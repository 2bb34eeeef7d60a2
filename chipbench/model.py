"""The model of a configuration file, as the benchmark sees it.

``Dims`` reads the published sizes (Hugging Face key names) of
``configs/<config>.json``; ``make_weights`` draws the weights of a seed.
Both belong to the benchmark, not to the program: the plain reference
(``reference.py``) and the program's first state (``harness.py``) are
made from the same ``make_weights`` call, so they start from the same
numbers without either taking anything the other made.
"""
from __future__ import annotations

import dataclasses
import math
import zlib

import jax
import jax.numpy as jnp

LAYER_KEYS = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w1", "w3", "w2")
NORMS = ("norm1", "norm2", "norm_f")


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int                  # hidden_size
    heads: int
    kv_heads: int
    hd: int                 # head_dim
    ff: int                 # intermediate_size
    vocab: int
    layers: int             # num_hidden_layers as run
    window: int | None      # sliding_window (None = full causal)
    rope_theta: float
    eps: float
    tied: bool
    scale_emb: float        # input embedding multiplier
    residual_scale: float   # multiplier of every block's output
    logit_scale: float      # multiplier of the logits

    @staticmethod
    def from_config(c: dict) -> "Dims":
        d = int(c["hidden_size"])
        # MiniCPM's muP constants: residual scale_depth / sqrt(depth) of the
        # published model (the cut depth stands for a stage of it), logits
        # divided by hidden_size / dim_model_base.
        depth = c.get("published", {}).get("num_hidden_layers",
                                           c["num_hidden_layers"])
        rs = (c["scale_depth"] / math.sqrt(depth)
              if c.get("scale_depth") else 1.0)
        ls = c["dim_model_base"] / d if c.get("dim_model_base") else 1.0
        return Dims(d=d, heads=int(c["num_attention_heads"]),
                    kv_heads=int(c["num_key_value_heads"]),
                    hd=int(c["head_dim"]), ff=int(c["intermediate_size"]),
                    vocab=int(c["vocab_size"]),
                    layers=int(c["num_hidden_layers"]),
                    window=c.get("sliding_window"),
                    rope_theta=float(c["rope_theta"]),
                    eps=float(c["rms_norm_eps"]),
                    tied=bool(c["tie_word_embeddings"]),
                    scale_emb=float(c.get("scale_emb") or 1.0),
                    residual_scale=rs, logit_scale=ls)

    def shapes(self) -> dict:
        """Logical f32 shape of every weight, layers stacked first."""
        L, d, f, q, kv = (self.layers, self.d, self.ff,
                          self.heads * self.hd, self.kv_heads * self.hd)
        layer = {"norm1": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
                 "wv": (L, d, kv), "wo": (L, q, d), "norm2": (L, d),
                 "w1": (L, d, f), "w3": (L, d, f), "w2": (L, f, d)}
        out = {"tok": (self.vocab, d), "norm_f": (d,), "layers": layer}
        if not self.tied:
            out["head"] = (d, self.vocab)
        return out

    def matmul_params(self) -> int:
        """Weights that enter a matrix multiplication per token (the
        projections, MLP and output head; not the embedding lookup)."""
        L, d = self.layers, self.d
        q, kv = self.heads * self.hd, self.kv_heads * self.hd
        per_layer = d * q + 2 * d * kv + q * d + 3 * d * self.ff
        return L * per_layer + d * self.vocab


def _std(name: str, shape: tuple) -> float:
    if name == "tok":
        return 0.02
    return 1.0 / math.sqrt(shape[-2])    # fan-in of a (.., in, out) matrix


def make_weight(key: jax.Array, name: str, shape: tuple) -> jax.Array:
    """One weight: ones for a norm, else a normal draw keyed by its name."""
    if name in NORMS:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.normal(k, shape, jnp.float32) * _std(name, shape)


def make_weights(key: jax.Array, dims: Dims) -> dict:
    """The whole logical weight tree of a seed's key (f32)."""
    shapes = dims.shapes()
    out = {n: make_weight(key, n, s) for n, s in shapes.items()
           if n != "layers"}
    out["layers"] = {n: make_weight(key, n, s)
                     for n, s in shapes["layers"].items()}
    return out


def leaf_names(dims: Dims) -> list[str]:
    """Compared leaves, in a fixed order: ``layers/<key>`` stands for L
    leaves, one per layer."""
    top = ["tok", "norm_f"] + ([] if dims.tied else ["head"])
    return top + [f"layers/{k}" for k in LAYER_KEYS]
