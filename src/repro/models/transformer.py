"""Decoder-only LM assembly for dense / vlm / moe / ssm / hybrid families.

One parameterized block covers all five families; layers are stacked and
scanned (FSDP gathers happen per layer inside the scan -- see
core/flatparam.py).  The hybrid (zamba2) model scans over "super-blocks"
(k mamba layers + one application of the *shared* attention block) so its
attention caches are sized by application count, not layer count.

All code runs inside a fully-manual shard_map; batch dims are the *local*
(dp-sharded) batch.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.flatparam import ParamGroup, ParamInfo
from repro.models import common as C
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.common import HeadLayout, KVCache
from repro.telemetry import profiler as PROF

LOCO_MIN_NUMEL = 2**16  # smaller tensors sync in bf16 (DESIGN.md §4)


def _loco(shape) -> bool:
    return math.prod(shape) >= LOCO_MIN_NUMEL


def _pi(name, shape, tp_dim=None, init="normal", init_scale=None, decay=True):
    return ParamInfo(
        name=name, shape=tuple(shape), tp_dim=tp_dim, init=init,
        init_scale=init_scale, loco=_loco(shape), decay=decay,
    )


def vocab_padded(cfg: ArchConfig, tp: int) -> int:
    return C.pad_to_multiple(cfg.vocab, tp)


# ---------------------------------------------------------------------------
# parameter declarations
# ---------------------------------------------------------------------------

def _attn_infos(cfg: ArchConfig, lay: HeadLayout, prefix=""):
    d, hd = cfg.d_model, lay.head_dim
    kv_tp = 1 if lay.kv_sharded else None
    infos = [
        _pi(prefix + "norm1", (d,), init="ones", decay=False),
        _pi(prefix + "wq", (d, lay.h_pad * hd), tp_dim=1),
        _pi(prefix + "wk", (d, lay.kv_pad * hd), tp_dim=kv_tp),
        _pi(prefix + "wv", (d, lay.kv_pad * hd), tp_dim=kv_tp),
        _pi(prefix + "wo", (lay.h_pad * hd, d), tp_dim=0),
    ]
    if cfg.qk_norm:
        infos += [
            _pi(prefix + "qnorm", (hd,), init="ones", decay=False),
            _pi(prefix + "knorm", (hd,), init="ones", decay=False),
        ]
    return infos


def _mlp_infos(cfg: ArchConfig, prefix=""):
    d, f = cfg.d_model, cfg.d_ff
    infos = [
        _pi(prefix + "norm2", (d,), init="ones", decay=False),
        _pi(prefix + "w1", (d, f), tp_dim=1),
        _pi(prefix + "w2", (f, d), tp_dim=0),
    ]
    if cfg.mlp in ("swiglu", "geglu"):
        infos.append(_pi(prefix + "w3", (d, f), tp_dim=1))
    return infos


def _moe_infos(cfg: ArchConfig):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    if cfg.moe_impl == "tp_dense":
        w_tp = (2, 1)  # (w1/w3 tp_dim, w2 tp_dim)
    else:
        w_tp = (0, 0)  # experts sharded
    infos = [
        _pi("norm2", (d,), init="ones", decay=False),
        _pi("router", (d, E)),
        _pi("w1", (E, d, f), tp_dim=w_tp[0], init_scale=1.0 / math.sqrt(d)),
        _pi("w2", (E, f, d), tp_dim=w_tp[1], init_scale=1.0 / math.sqrt(f)),
    ]
    if cfg.mlp in ("swiglu", "geglu"):
        infos.append(_pi("w3", (E, d, f), tp_dim=w_tp[0], init_scale=1.0 / math.sqrt(d)))
    if cfg.n_shared_experts:
        # deepseek-style always-on experts: one dense TP-sliced FFN of width
        # n_shared_experts * d_ff alongside the routed experts
        fs = cfg.n_shared_experts * f
        infos += [
            _pi("ws1", (d, fs), tp_dim=1, init_scale=1.0 / math.sqrt(d)),
            _pi("ws2", (fs, d), tp_dim=0, init_scale=1.0 / math.sqrt(fs)),
        ]
        if cfg.mlp in ("swiglu", "geglu"):
            infos.append(_pi("ws3", (d, fs), tp_dim=1,
                             init_scale=1.0 / math.sqrt(d)))
    return infos


def _mamba_infos(cfg: ArchConfig, prefix=""):
    d, dil, N, H, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.d_conv
    return [
        _pi(prefix + "normm", (d,), init="ones", decay=False),
        _pi(prefix + "w_z", (d, dil), tp_dim=1),
        _pi(prefix + "w_x", (d, dil), tp_dim=1),
        _pi(prefix + "w_B", (d, N)),
        _pi(prefix + "w_C", (d, N)),
        _pi(prefix + "w_dt", (d, H), tp_dim=1),
        _pi(prefix + "dt_bias", (H,), tp_dim=0, init="zeros", decay=False),
        _pi(prefix + "A_log", (H,), tp_dim=0, init="zeros", decay=False),
        _pi(prefix + "D", (H,), tp_dim=0, init="ones", decay=False),
        _pi(prefix + "conv_x", (K, dil), tp_dim=1, init_scale=1.0 / math.sqrt(K)),
        _pi(prefix + "conv_B", (K, N), init_scale=1.0 / math.sqrt(K)),
        _pi(prefix + "conv_C", (K, N), init_scale=1.0 / math.sqrt(K)),
        _pi(prefix + "normg", (dil,), tp_dim=0, init="ones", decay=False),
        _pi(prefix + "w_out", (dil, d), tp_dim=0),
    ]


def head_layout(cfg: ArchConfig, tp: int) -> HeadLayout:
    return HeadLayout.make(cfg.n_heads, cfg.n_kv_heads, cfg.hd, tp)


def build_groups(cfg: ArchConfig, tp: int) -> list[ParamGroup]:
    vp = vocab_padded(cfg, tp)
    d = cfg.d_model
    groups = [
        ParamGroup("embed", (
            _pi("tok", (vp, d), tp_dim=0, init="embed", init_scale=0.02),
        )),
        ParamGroup("final", tuple(
            [_pi("norm_f", (d,), init="ones", decay=False)]
            + ([] if cfg.tied_embeddings else [_pi("head", (d, vp), tp_dim=1)])
        )),
    ]
    lay = head_layout(cfg, tp) if cfg.family != "ssm" else None

    if cfg.family in ("dense", "vlm"):
        infos = _attn_infos(cfg, lay) + _mlp_infos(cfg)
        groups.append(ParamGroup("block", tuple(infos), n_layers=cfg.n_layers))
    elif cfg.family == "moe":
        infos = _attn_infos(cfg, lay) + _moe_infos(cfg)
        groups.append(ParamGroup("block", tuple(infos), n_layers=cfg.n_layers))
    elif cfg.family == "ssm":
        groups.append(ParamGroup("block", tuple(_mamba_infos(cfg)), n_layers=cfg.n_layers))
    elif cfg.family == "hybrid":
        groups.append(ParamGroup("block", tuple(_mamba_infos(cfg)), n_layers=cfg.n_layers))
        shared = _attn_infos(cfg, lay, prefix="s_") + _mlp_infos(cfg, prefix="s_")
        groups.append(ParamGroup("shared", tuple(shared)))
    else:
        raise ValueError(cfg.family)
    return groups


# ---------------------------------------------------------------------------
# block forwards
# ---------------------------------------------------------------------------

def _qkv(p, x, lay: HeadLayout, cfg: ArchConfig, positions, prefix=""):
    B, S, _ = x.shape
    hd = lay.head_dim
    q = C.col_linear(x, p[prefix + "wq"]).reshape(B, S, lay.hl, hd)
    k = C.col_linear(x, p[prefix + "wk"]).reshape(B, S, lay.kvl, hd)
    v = C.col_linear(x, p[prefix + "wv"]).reshape(B, S, lay.kvl, hd)
    if cfg.qk_norm:
        q = C.rmsnorm(q, p[prefix + "qnorm"])
        k = C.rmsnorm(k, p[prefix + "knorm"])
    q = C.rope(q, positions, cfg.rope_theta)
    k = C.rope(k, positions, cfg.rope_theta)
    return q, k, v


def _layer_window(cfg: ArchConfig, layer_idx):
    """Dynamic per-layer window (int32) -- 2**30 means effectively full."""
    full = jnp.int32(1 << 30)
    if cfg.attn_kind == "swa":
        return jnp.int32(cfg.window)
    if cfg.attn_kind == "local_global":
        return jnp.where(layer_idx % 2 == 0, jnp.int32(cfg.window), full)
    return full


def attention_block(p, x, cfg: ArchConfig, lay: HeadLayout, layer_idx, positions,
                    cache: KVCache | None, prefix="", sp: bool = False):
    """Returns (attn_out (pre-residual), new_cache).

    sp: x is the (B, S/TP, d) sequence shard; norm runs on the shard, the
    block gathers to full S for attention and returns a scattered shard
    (Megatron sequence parallelism)."""
    with PROF.layer("attention"):
        h = C.norm(cfg.norm, x, p[prefix + "norm1"])
        h = C.sp_gather(h, sp) if sp else h
        B, S, d = h.shape
        q, k, v = _qkv(p, h, lay, cfg, positions, prefix)
        window = _layer_window(cfg, layer_idx)
        kv_map = lay.kv_map()

        cp = C.cp_degree(lay)

        if cache is None:
            kq, vq = C.expand_kv(k, kv_map), C.expand_kv(v, kv_map)
            out = C.blockwise_attention(
                q, kq, vq, positions, positions,
                causal=True, window=window, softcap=cfg.attn_softcap,
            )
            new_cache = None
        elif S > 1:
            # prefill into the cache; attention over the in-flight k/v
            # directly (the cache was empty), then persist -- window-sharded
            # when kv heads are TP-replicated (see common.py cp_* docs).
            kq, vq = C.expand_kv(k, kv_map), C.expand_kv(v, kv_map)
            out = C.blockwise_attention(
                q, kq, vq, positions, positions,
                causal=True, window=window, softcap=cfg.attn_softcap,
            )
            if cp > 1:
                new_cache = C.build_cp_cache(k, v, cache.k.shape[1], cp,
                                             dtype=cache.k.dtype)
            else:
                new_cache = cache.append(k, v, positions[0])
        else:
            # single-token decode
            if cp > 1:
                new_cache = C.cp_append(cache, k, v, positions[0], cp)
                out = C.cp_decode_attention(
                    q, new_cache, lay.kv_map_global(), positions,
                    window=window, softcap=cfg.attn_softcap)
            else:
                new_cache = cache.append(k, v, positions[0])
                kq = C.expand_kv(new_cache.k, kv_map)
                vq = C.expand_kv(new_cache.v, kv_map)
                out = C.blockwise_attention(
                    q, kq, vq, positions, new_cache.pos,
                    causal=True, window=window, softcap=cfg.attn_softcap,
                )
        out = out.reshape(B, S, lay.hl * lay.head_dim)
        return C.row_linear(out, p[prefix + "wo"], sp=sp), new_cache


def mlp_block(p, x, cfg: ArchConfig, prefix="", sp: bool = False):
    with PROF.layer("mlp"):
        h = C.norm(cfg.norm, x, p[prefix + "norm2"])
        h = C.sp_gather(h, sp) if sp else h
        a = C.col_linear(h, p[prefix + "w1"])
        if cfg.mlp in ("swiglu", "geglu"):
            b = C.col_linear(h, p[prefix + "w3"])
            act = (jax.nn.silu(a) if cfg.mlp == "swiglu"
                   else jax.nn.gelu(a)) * b
        else:
            act = jax.nn.gelu(a)
        return C.row_linear(act, p[prefix + "w2"], sp=sp)


def _res(cfg: ArchConfig, x, delta):
    s = cfg.residual_scale or 1.0
    return x + s * delta


def dense_block(p, x, cfg, lay, layer_idx, positions, cache, sp: bool = False):
    if cfg.parallel_block:
        h_in = x
        a, new_cache = attention_block(p, h_in, cfg, lay, layer_idx, positions,
                                       cache, sp=sp)
        m = mlp_block(p, h_in, cfg, sp=sp)
        return _res(cfg, x, a + m), new_cache, {}
    a, new_cache = attention_block(p, x, cfg, lay, layer_idx, positions, cache,
                                   sp=sp)
    x = _res(cfg, x, a)
    x = _res(cfg, x, mlp_block(p, x, cfg, sp=sp))
    return x, new_cache, {}


def moe_layer(p, x, cfg, lay, layer_idx, positions, cache, sp: bool = False,
              a2a_state=None):
    a, new_cache = attention_block(p, x, cfg, lay, layer_idx, positions, cache,
                                   sp=sp)
    x = _res(cfg, x, a)
    with PROF.layer("mlp"):
        h = C.norm(cfg.norm, x, p["norm2"])
        h = C.sp_gather(h, sp) if sp else h
        y, aux = MOE.moe_block(h, p, cfg, sp=sp, a2a_state=a2a_state)
    x = _res(cfg, x, y)
    return x, new_cache, aux


def mamba_layer(p, x, cfg, conv_cache, ssm_state, single_step, prefix="",
                sp: bool = False):
    h = C.norm("rmsnorm", x, p[prefix + "normm"])
    h = C.sp_gather(h, sp) if sp else h
    pp = {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}
    y, (cc, S) = SSM.mamba2_mixer(
        h, pp, cfg, conv_cache=conv_cache, ssm_state=ssm_state,
        single_step=single_step, sp=sp
    )
    return _res(cfg, x, y), cc, S


# ---------------------------------------------------------------------------
# cache pytrees (per family)
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    """Per-model decode cache; unused fields are () for the family."""

    kv: Any          # stacked KVCache arrays or ()
    conv: Any        # stacked conv caches or ()
    ssm: Any         # stacked ssm states or ()
    pos: jax.Array   # scalar int32: next absolute position


def init_decode_state(cfg: ArchConfig, tp: int, batch_local: int, window: int,
                      dtype=jnp.bfloat16) -> DecodeState:
    pos = jnp.int32(0)
    if cfg.family in ("dense", "vlm", "moe"):
        lay = head_layout(cfg, tp)
        w = min(window, cfg.window) if cfg.attn_kind == "swa" else window
        cp = C.cp_degree(lay)
        w = -(-w // cp)  # per-rank window shard when kv replicated (ceil)
        kv = KVCache.create(batch_local, w, lay.kvl, lay.head_dim, dtype)
        kv = jax.tree.map(lambda a: jnp.stack([a] * cfg.n_layers), kv)
        return DecodeState(kv=kv, conv=(), ssm=(), pos=pos)
    if cfg.family == "ssm":
        conv = _conv_zeros(cfg, tp, batch_local, cfg.n_layers)
        ssm = jnp.zeros((cfg.n_layers, batch_local, cfg.ssm_heads // tp,
                         cfg.ssm_state, cfg.ssm_headdim), jnp.float32)
        return DecodeState(kv=(), conv=conv, ssm=ssm, pos=pos)
    if cfg.family == "hybrid":
        lay = head_layout(cfg, tp)
        n_apps = cfg.n_layers // cfg.hybrid_attn_every
        kv = KVCache.create(batch_local, window, lay.kvl, lay.head_dim, dtype)
        kv = jax.tree.map(lambda a: jnp.stack([a] * n_apps), kv)
        conv = _conv_zeros(cfg, tp, batch_local, cfg.n_layers)
        ssm = jnp.zeros((cfg.n_layers, batch_local, cfg.ssm_heads // tp,
                         cfg.ssm_state, cfg.ssm_headdim), jnp.float32)
        return DecodeState(kv=kv, conv=conv, ssm=ssm, pos=pos)
    raise ValueError(cfg.family)


def _conv_zeros(cfg, tp, batch_local, n_layers):
    K = cfg.d_conv
    dil = cfg.d_inner // tp
    N = cfg.ssm_state
    return (
        jnp.zeros((n_layers, batch_local, K - 1, dil), jnp.bfloat16),
        jnp.zeros((n_layers, batch_local, K - 1, N), jnp.bfloat16),
        jnp.zeros((n_layers, batch_local, K - 1, N), jnp.bfloat16),
    )


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecoderLM:
    cfg: ArchConfig
    tp: int
    sp: bool = False  # Megatron sequence parallelism (training path only)

    def groups(self) -> list[ParamGroup]:
        return build_groups(self.cfg, self.tp)

    # ---- embedding / logits -------------------------------------------------
    def _embed(self, store, tokens, sp: bool = False):
        with PROF.layer("embed"):
            emb = store.group("embed")["tok"]
            x = C.vocab_parallel_embed(emb, tokens, sp=sp)
            if self.cfg.emb_scale:
                x = x * self.cfg.emb_scale
            return x, emb

    def _logits(self, store, x, emb):
        with PROF.layer("head"):
            fin = store.group("final")
            x = C.norm(self.cfg.norm, x, fin["norm_f"])
            w = emb.T if self.cfg.tied_embeddings else fin["head"]
            logits = C.vocab_parallel_logits(x, w)
            if self.cfg.logit_scale:
                logits = logits * self.cfg.logit_scale
            return logits

    # ---- full forward over a sequence (train / prefill) --------------------
    def forward(self, store, tokens, *, caches: DecodeState | None = None,
                remat: bool = True, moe_a2a_state=None):
        """tokens: (B, S) -> (local_logits (B, S, V_local), aux, new_caches).

        ``moe_a2a_state``: optional ``(n_layers, state_len)`` per-layer MoE
        combine error-feedback stack (moe_a2a_codec="block8+ef"); when
        passed, the updated stack rides back as ``aux["moe_a2a_state"]``.
        """
        cfg = self.cfg
        B, S = tokens.shape
        positions = jnp.arange(S, dtype=jnp.int32)
        sp = (self.sp and caches is None and self.tp > 1 and S % self.tp == 0)
        x, emb = self._embed(store, tokens, sp=sp)
        aux0 = {"aux": jnp.float32(0), "z": jnp.float32(0)}

        if caches is not None:
            # serving prefill: statically-unrolled layer loop (see decode_step
            # for why: scan xs/ys copies the weight stacks and caches).
            x, aux, new_caches = self._prefill_unrolled(store, x, positions,
                                                        caches, aux0)
        elif cfg.family in ("dense", "vlm", "moe"):
            lay = head_layout(cfg, self.tp)
            xs = store.scan_xs("block")
            idxs = jnp.arange(cfg.n_layers)
            ef = moe_a2a_state  # (L, state_len) or None

            def body(carry, sl):
                xc, aux = carry
                if ef is not None:
                    xs_slice, idx, ef_l = sl
                else:
                    (xs_slice, idx), ef_l = sl, None
                p = store.materialize_slice("block", xs_slice)
                if cfg.family == "moe":
                    xc, _nc, a = moe_layer(p, xc, cfg, lay, idx, positions, None,
                                           sp=sp, a2a_state=ef_l)
                    new_ef = a.pop("a2a_state", None)
                    aux = {k: aux[k] + a[k] for k in aux}
                else:
                    xc, _nc, _ = dense_block(p, xc, cfg, lay, idx, positions, None,
                                             sp=sp)
                    new_ef = None
                return (xc, aux), new_ef

            if remat:
                body = jax.checkpoint(body, prevent_cse=False)
            sl_xs = (xs, idxs) if ef is None else (xs, idxs, ef)
            with PROF.layer("layers"):
                (x, aux), new_ef_stack = jax.lax.scan(body, (x, aux0), sl_xs)
            if ef is not None:
                aux = {**aux, "moe_a2a_state": new_ef_stack}
            new_caches = None

        elif cfg.family == "ssm":
            xs = store.scan_xs("block")

            def body(carry, xs_slice):
                xc, aux = carry
                p = store.materialize_slice("block", xs_slice)
                xc, _cc, _S = mamba_layer(p, xc, cfg, None, None, False, sp=sp)
                return (xc, aux), None

            if remat:
                body = jax.checkpoint(body, prevent_cse=False)
            with PROF.layer("layers"):
                (x, aux), _ = jax.lax.scan(body, (x, aux0), xs)
            new_caches = None

        elif cfg.family == "hybrid":
            with PROF.layer("layers"):
                x, aux, new_caches = self._hybrid_forward(
                    store, x, positions, None, aux0, remat, sp=sp)
        else:
            raise ValueError(cfg.family)

        x = C.sp_gather(x, sp) if sp else x  # exit sequence parallelism
        logits = self._logits(store, x, emb)
        return logits, aux, new_caches

    def _hybrid_forward(self, store, x, positions, caches, aux0, remat,
                        sp: bool = False):
        """Training path (caches handled by _prefill_unrolled)."""
        cfg = self.cfg
        k = cfg.hybrid_attn_every
        n_super = cfg.n_layers // k
        lay = head_layout(cfg, self.tp)
        shared = store.group("shared")
        xs = store.scan_xs("block")
        xs = jax.tree.map(lambda a: a.reshape(n_super, k, *a.shape[1:]), xs)

        def super_body(carry, sl):
            xc, aux = carry
            xs_s, sidx = sl

            def inner(xc2, xs_slice):
                p = store.materialize_slice("block", xs_slice)
                xc2, _cc, _S = mamba_layer(p, xc2, cfg, None, None, False, sp=sp)
                return xc2, None

            xc, _ = jax.lax.scan(inner, xc, xs_s)
            a, _nc = attention_block(shared, xc, cfg, lay, sidx, positions, None,
                                     prefix="s_", sp=sp)
            xc = _res(cfg, xc, a)
            xc = _res(cfg, xc, mlp_block(shared, xc, cfg, prefix="s_", sp=sp))
            return (xc, aux), None

        if remat:
            super_body = jax.checkpoint(super_body, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(super_body, (x, aux0), (xs, jnp.arange(n_super)))
        return x, aux, None

    def _prefill_unrolled(self, store, x, positions, caches, aux0):
        """Serving prefill: scan over layers with caches in the carry
        (same pattern and rationale as decode_step)."""
        cfg = self.cfg
        S = x.shape[1]
        xs = store.scan_xs("block")

        def _at(tree, idx):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False),
                tree)

        def _put(tree, new, idx):
            return jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(
                    a, n.astype(a.dtype), idx, 0),
                tree, new)

        if cfg.family in ("dense", "vlm", "moe"):
            lay = head_layout(cfg, self.tp)

            def body(carry, sl):
                xc, aux, kv = carry
                xs_slice, idx = sl
                p = store.materialize_slice("block", xs_slice)
                cache = KVCache(*_at(kv, idx))
                if cfg.family == "moe":
                    xc, nc, a = moe_layer(p, xc, cfg, lay, idx, positions, cache)
                    aux = {k: aux[k] + a[k] for k in aux}
                else:
                    xc, nc, _ = dense_block(p, xc, cfg, lay, idx, positions, cache)
                return (xc, aux, _put(kv, tuple(nc), idx)), None

            (x, aux, kv), _ = jax.lax.scan(
                body, (x, aux0, tuple(caches.kv)), (xs, jnp.arange(cfg.n_layers)))
            return x, aux, caches._replace(kv=KVCache(*kv), pos=caches.pos + S)

        if cfg.family == "ssm":

            def body(carry, sl):
                xc, conv, ssm = carry
                xs_slice, idx = sl
                p = store.materialize_slice("block", xs_slice)
                s_i = jax.lax.dynamic_index_in_dim(ssm, idx, 0, keepdims=False)
                xc, cc, Snew = mamba_layer(p, xc, cfg, _at(conv, idx), s_i, False)
                conv = _put(conv, cc, idx)
                ssm = jax.lax.dynamic_update_index_in_dim(ssm, Snew, idx, 0)
                return (xc, conv, ssm), None

            (x, conv, ssm), _ = jax.lax.scan(
                body, (x, caches.conv, caches.ssm), (xs, jnp.arange(cfg.n_layers)))
            return x, aux0, caches._replace(conv=conv, ssm=ssm, pos=caches.pos + S)

        # hybrid
        k = cfg.hybrid_attn_every
        n_super = cfg.n_layers // k
        lay = head_layout(cfg, self.tp)
        shared = store.group("shared")
        xs_r = jax.tree.map(lambda a: a.reshape(n_super, k, *a.shape[1:]), xs)

        def super_body(carry, sl):
            xc, conv, ssm, kv = carry
            xs_s, sidx = sl

            def inner(carry2, sl2):
                xc2, conv2, ssm2 = carry2
                xs_slice, j = sl2
                li = sidx * k + j
                p = store.materialize_slice("block", xs_slice)
                s_li = jax.lax.dynamic_index_in_dim(ssm2, li, 0, keepdims=False)
                xc2, cc, Snew = mamba_layer(p, xc2, cfg, _at(conv2, li), s_li, False)
                conv2 = _put(conv2, cc, li)
                ssm2 = jax.lax.dynamic_update_index_in_dim(ssm2, Snew, li, 0)
                return (xc2, conv2, ssm2), None

            (xc, conv, ssm), _ = jax.lax.scan(
                inner, (xc, conv, ssm), (xs_s, jnp.arange(k)))
            cache = KVCache(*_at(kv, sidx))
            a, nc = attention_block(shared, xc, cfg, lay, sidx, positions, cache,
                                    prefix="s_")
            xc = _res(cfg, xc, a)
            xc = _res(cfg, xc, mlp_block(shared, xc, cfg, prefix="s_"))
            return (xc, conv, ssm, _put(kv, tuple(nc), sidx)), None

        (x, conv, ssm, kv), _ = jax.lax.scan(
            super_body, (x, caches.conv, caches.ssm, tuple(caches.kv)),
            (xs_r, jnp.arange(n_super)))
        return x, aux0, DecodeState(kv=KVCache(*kv), conv=conv, ssm=ssm,
                                    pos=caches.pos + S)

    # ---- losses -------------------------------------------------------------
    def loss_fn(self, store, batch, remat: bool = True, moe_a2a_state=None):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits, aux, _ = self.forward(store, inputs, remat=remat,
                                      moe_a2a_state=moe_a2a_state)
        new_ef = aux.pop("moe_a2a_state", None)
        with PROF.layer("head"):
            loss = C.vocab_parallel_xent(
                logits, targets, self.cfg.vocab, softcap=self.cfg.final_softcap
            )
        total = loss
        if self.cfg.n_experts:
            total = total + self.cfg.aux_loss_coef * aux["aux"] + self.cfg.router_z_coef * aux["z"]
        out = {"ce": loss, **aux}
        if new_ef is not None:
            out["moe_a2a_state"] = new_ef  # non-scalar: steps.py pops it
        return total, out

    # ---- decode -------------------------------------------------------------
    def decode_step(self, store, state: DecodeState, token):
        """token: (B, 1) int32 -> (local_logits (B, 1, Vl), new_state)."""
        cfg = self.cfg
        pos = state.pos
        positions = pos[None] + jnp.arange(1, dtype=jnp.int32)
        x, emb = self._embed(store, token)

        # Caches are carried through the layer scan and updated in place
        # with dynamic_update_index.  (A statically-unrolled variant was
        # tried and REFUTED: XLA:CPU liveness keeps every layer's buffers
        # alive -- mixtral prefill ballooned 25 -> 137 GiB.  The scan-carry
        # form is also the TPU-correct pattern: loop-invariant xs and
        # DUS-carried caches alias in place there.  EXPERIMENTS.md §Perf.)
        def _at(tree, idx):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False),
                tree)

        def _put(tree, new, idx):
            return jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(
                    a, n.astype(a.dtype), idx, 0),
                tree, new)

        if cfg.family in ("dense", "vlm", "moe"):
            lay = head_layout(cfg, self.tp)
            xs = store.scan_xs("block")
            idxs = jnp.arange(cfg.n_layers)

            def body(carry, sl):
                xc, kv = carry
                xs_slice, idx = sl
                p = store.materialize_slice("block", xs_slice)
                cache = KVCache(*_at(kv, idx))
                if cfg.family == "moe":
                    xc, nc, _ = moe_layer(p, xc, cfg, lay, idx, positions, cache)
                else:
                    xc, nc, _ = dense_block(p, xc, cfg, lay, idx, positions, cache)
                return (xc, _put(kv, tuple(nc), idx)), None

            (x, new_kv), _ = jax.lax.scan(body, (x, tuple(state.kv)), (xs, idxs))
            new_state = state._replace(kv=KVCache(*new_kv), pos=pos + 1)

        elif cfg.family == "ssm":
            xs = store.scan_xs("block")
            idxs = jnp.arange(cfg.n_layers)

            def body(carry, sl):
                xc, conv, ssm = carry
                xs_slice, idx = sl
                p = store.materialize_slice("block", xs_slice)
                xc, cc, Snew = mamba_layer(p, xc, cfg, _at(conv, idx),
                                           _at(ssm, idx), True)
                conv = _put(conv, cc, idx)
                ssm = jax.lax.dynamic_update_index_in_dim(ssm, Snew, idx, 0)
                return (xc, conv, ssm), None

            (x, new_conv, new_ssm), _ = jax.lax.scan(
                body, (x, state.conv, state.ssm), (xs, idxs))
            new_state = state._replace(conv=new_conv, ssm=new_ssm, pos=pos + 1)

        elif cfg.family == "hybrid":
            k = cfg.hybrid_attn_every
            n_super = cfg.n_layers // k
            lay = head_layout(cfg, self.tp)
            shared = store.group("shared")
            xs = store.scan_xs("block")
            xs_r = jax.tree.map(lambda a: a.reshape(n_super, k, *a.shape[1:]), xs)

            def super_body(carry, sl):
                xc, conv, ssm, kv = carry
                xs_s, sidx = sl

                def inner(carry2, sl2):
                    xc2, conv2, ssm2 = carry2
                    xs_slice, j = sl2
                    li = sidx * k + j
                    p = store.materialize_slice("block", xs_slice)
                    s_li = jax.lax.dynamic_index_in_dim(ssm2, li, 0, keepdims=False)
                    xc2, cc, Snew = mamba_layer(p, xc2, cfg, _at(conv2, li),
                                                s_li, True)
                    conv2 = _put(conv2, cc, li)
                    ssm2 = jax.lax.dynamic_update_index_in_dim(ssm2, Snew, li, 0)
                    return (xc2, conv2, ssm2), None

                (xc, conv, ssm), _ = jax.lax.scan(
                    inner, (xc, conv, ssm), (xs_s, jnp.arange(k)))
                cache = KVCache(*_at(kv, sidx))
                a, nc = attention_block(shared, xc, cfg, lay, sidx, positions,
                                        cache, prefix="s_")
                xc = _res(cfg, xc, a)
                xc = _res(cfg, xc, mlp_block(shared, xc, cfg, prefix="s_"))
                return (xc, conv, ssm, _put(kv, tuple(nc), sidx)), None

            (x, new_conv, new_ssm, new_kv), _ = jax.lax.scan(
                super_body, (x, state.conv, state.ssm, tuple(state.kv)),
                (xs_r, jnp.arange(n_super)))
            new_state = DecodeState(kv=KVCache(*new_kv), conv=new_conv,
                                    ssm=new_ssm, pos=pos + 1)
        else:
            raise ValueError(cfg.family)

        logits = self._logits(store, x, emb)
        if self.cfg.final_softcap:
            logits = self.cfg.final_softcap * jnp.tanh(logits / self.cfg.final_softcap)
        return logits, new_state
