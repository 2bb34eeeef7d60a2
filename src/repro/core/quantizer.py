"""Quantization codecs used by LoCo and the baseline compressors.

Two gradient codecs (paper Eqn. (1) and the block-scaled variant):

* ``fixed``  -- paper-exact: ``q = round(x * s)`` clipped to the signed p-bit
  range, ``deq = float(q) / s`` with a *static* scale ``s`` (2**17 / 2**19 in
  the paper).
* ``block``  -- beyond-paper default: per-block (256 elements) absmax dynamic
  scale.  Removes the clipping hyper-parameter; costs one f32 scale per block
  on the wire (~1.6% at 4-bit).
* ``tensor`` -- one absmax dynamic scale for the whole segment.  Cheapest
  metadata (4 bytes per segment) but the scale is *per-node dynamic*, so it
  must cross the wire per peer (a ``gather`` leaf in the codec registry) —
  unlike ``fixed``, whose scale is a static config constant every peer
  already knows.

plus the 8-bit error codecs:

* ``int8 + s_e``       -- paper-exact error storage (Eqn. (7)).
* ``float8_e4m3 * s8`` -- TPU-native production storage with a static
  pre-scale; used by the in-backward hijack path (cotangent dtype must be
  the primal dtype, which rules out int8 there).

All functions are pure jnp and shard_map-safe (elementwise / local only).
The Pallas kernels in ``repro.kernels`` implement fused fast paths for the
same math; ``repro/kernels/ref.py`` delegates to this module as the oracle.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

INT4_MIN, INT4_MAX = -8, 7
INT8_MIN, INT8_MAX = -128, 127
DEFAULT_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Static configuration of the gradient wire format."""

    bits: int = 4
    mode: Literal["fixed", "block", "tensor"] = "block"
    scale: float = 2.0**17          # fixed mode only (paper: 2**17 or 2**19)
    block: int = DEFAULT_BLOCK      # block mode only
    # 8-bit error codec ("int8" = paper-exact, "f8" = TPU production path)
    error_codec: Literal["int8", "f8", "bf16", "none"] = "f8"
    error_scale: float = 2.0**14    # static pre-scale for int8/f8 error
    stochastic_rounding: bool = False

    @property
    def qmin(self) -> int:
        return -(2 ** (self.bits - 1))

    @property
    def qmax(self) -> int:
        return 2 ** (self.bits - 1) - 1


def _round(x: jax.Array, cfg: QuantConfig, key: jax.Array | None) -> jax.Array:
    if cfg.stochastic_rounding and key is not None:
        noise = jax.random.uniform(key, x.shape, x.dtype) - 0.5
        return jnp.round(x + noise)
    return jnp.round(x)


# ---------------------------------------------------------------------------
# fixed-scale codec (paper Eqn. (1))
# ---------------------------------------------------------------------------

def quant_fixed(x: jax.Array, cfg: QuantConfig, key: jax.Array | None = None) -> jax.Array:
    """compressor(x; s, p): round to nearest integer in the signed p-bit range."""
    q = _round(x.astype(jnp.float32) * cfg.scale, cfg, key)
    return jnp.clip(q, cfg.qmin, cfg.qmax).astype(jnp.int8)


def dequant_fixed(q: jax.Array, cfg: QuantConfig) -> jax.Array:
    """decompressor(q; s) = float(q) / s."""
    return q.astype(jnp.float32) / cfg.scale


# ---------------------------------------------------------------------------
# block-scaled codec (beyond paper; Zero++-style absmax blocks)
# ---------------------------------------------------------------------------

def _to_blocks(x: jax.Array, block: int) -> jax.Array:
    assert x.ndim == 1, "block codec operates on flat vectors"
    n = x.shape[0]
    assert n % block == 0, f"size {n} not a multiple of block {block}"
    return x.reshape(n // block, block)


def quant_block(
    x: jax.Array, cfg: QuantConfig, key: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Per-block absmax quantization.  Returns (int8 codes, f32 scales).

    codes[i] = round(x[i] * scale_b), scale_b = qmax / absmax(block b).
    """
    xb = _to_blocks(x.astype(jnp.float32), cfg.block)
    absmax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    scales = jnp.float32(cfg.qmax) / jnp.maximum(absmax, 1e-30)
    q = _round(xb * scales, cfg, key)
    q = jnp.clip(q, cfg.qmin, cfg.qmax).astype(jnp.int8)
    return q.reshape(-1), scales.reshape(-1)


def dequant_block(q: jax.Array, scales: jax.Array, cfg: QuantConfig) -> jax.Array:
    qb = _to_blocks(q.astype(jnp.float32), cfg.block)
    return (qb / scales.reshape(-1, 1)).reshape(-1)


# ---------------------------------------------------------------------------
# tensor-scaled codec (one dynamic absmax scale per segment)
# ---------------------------------------------------------------------------

def quant_tensor(
    x: jax.Array, cfg: QuantConfig, key: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Whole-segment absmax quantization.  Returns (int8 codes, (1,) f32 scale).

    The scale is *dynamic per node* (each peer's absmax differs), so a
    receiver must dequantize each peer's payload with that peer's scale —
    the codec registry exchanges it as a ``gather`` wire leaf.
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf))
    scale = jnp.float32(cfg.qmax) / jnp.maximum(absmax, 1e-30)
    q = _round(xf * scale, cfg, key)
    q = jnp.clip(q, cfg.qmin, cfg.qmax).astype(jnp.int8)
    return q, scale.reshape(1)


# ---------------------------------------------------------------------------
# sub-byte packing (wire format): half-split int4, eighth-split signs
# ---------------------------------------------------------------------------

PACK_GRANULE = 256  # elements per packing granule (= one kernel row, QBLOCK)
SIGN_PACK = 8       # signs per wire byte


def _granules(x: jax.Array, parts: int) -> tuple[jax.Array, int]:
    """View the last axis as granules of ``PACK_GRANULE`` elements (or one
    granule when it is shorter) split into ``parts`` contiguous slices."""
    n = x.shape[-1]
    g = min(n, PACK_GRANULE)
    assert n % g == 0 and g % parts == 0, (x.shape, parts)
    return x.reshape(*x.shape[:-1], n // g, g), g // parts


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int8-held int4 values (in [-8, 7]) into half-length int8.

    Layout, per granule of 256 elements: byte ``k`` holds element ``k`` in
    its low nibble and element ``k + 128`` in its high nibble.  Both halves
    are contiguous 128-lane slices, so the TPU tiles every operand densely
    (an interleaved even/odd layout leaves a minor dimension of 2, padded
    64x).  The shifts run in int32: ``(hi << 4) | (lo & 0xF)`` of signed
    nibbles is already in the int8 range.
    """
    qg, w = _granules(q.astype(jnp.int32), 2)
    lo, hi = qg[..., :w], qg[..., w:]
    return ((hi << 4) | (lo & 0xF)).astype(jnp.int8).reshape(
        *q.shape[:-1], q.shape[-1] // 2)


def unpack_int4(p: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`; returns int8 values in [-8, 7]."""
    b = p.astype(jnp.int32)
    lo = ((b & 0xF) ^ 8) - 8          # sign-extend the low nibble
    hi = b >> 4                        # arithmetic shift sign-extends
    n = p.shape[-1] * 2
    g = min(n, PACK_GRANULE)
    lo = lo.reshape(*p.shape[:-1], n // g, g // 2)
    hi = hi.reshape(*p.shape[:-1], n // g, g // 2)
    out = jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8)
    return out.reshape(*p.shape[:-1], n)


def pack_signs(bits: jax.Array) -> jax.Array:
    """Pack 0/1 sign bits into uint8 bytes, 8 per byte.

    Layout, per granule of 256 elements: bit ``j`` of byte ``k`` is
    element ``32 j + k`` -- the eight contiguous 32-lane slices of the
    granule, the same split-not-interleave rule as :func:`pack_int4`, so
    the Pallas sign-pack kernel produces identical bytes from whole slices.
    """
    bg, w = _granules(bits.astype(jnp.int32), SIGN_PACK)
    out = bg[..., :w]
    for j in range(1, SIGN_PACK):
        out = out | (bg[..., j * w:(j + 1) * w] << j)
    return out.astype(jnp.uint8).reshape(
        *bits.shape[:-1], bits.shape[-1] // SIGN_PACK)


def unpack_signs(p: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_signs`; returns uint8 values in {0, 1}."""
    b = p.astype(jnp.int32)
    n = p.shape[-1] * SIGN_PACK
    g = min(n, PACK_GRANULE)
    b = b.reshape(*p.shape[:-1], n // g, g // SIGN_PACK)
    out = jnp.concatenate([(b >> j) & 1 for j in range(SIGN_PACK)], axis=-1)
    return out.astype(jnp.uint8).reshape(*p.shape[:-1], n)


# ---------------------------------------------------------------------------
# 8-bit error codecs (paper Eqn. (7) and the TPU f8 variant)
# ---------------------------------------------------------------------------

def error_encode(e: jax.Array, cfg: QuantConfig) -> jax.Array:
    """High-precision error -> 8-bit storage."""
    if cfg.error_codec == "none":
        return e.astype(jnp.float32)
    if cfg.error_codec == "bf16":
        return e.astype(jnp.bfloat16)
    if cfg.error_codec == "int8":
        q = jnp.round(e.astype(jnp.float32) * cfg.error_scale)
        return jnp.clip(q, INT8_MIN, INT8_MAX).astype(jnp.int8)
    if cfg.error_codec == "f8":
        scaled = e.astype(jnp.float32) * cfg.error_scale
        # saturate to f8_e4m3 range to avoid inf/nan on outliers
        scaled = jnp.clip(scaled, -448.0, 448.0)
        return scaled.astype(jnp.float8_e4m3fn)
    raise ValueError(cfg.error_codec)


def error_decode(e8: jax.Array, cfg: QuantConfig) -> jax.Array:
    """8-bit storage -> float32 error (decompressor(e; s_e))."""
    if cfg.error_codec in ("none", "bf16"):
        return e8.astype(jnp.float32)
    return e8.astype(jnp.float32) / cfg.error_scale


def error_dtype(cfg: QuantConfig):
    return {
        "none": jnp.float32,
        "bf16": jnp.bfloat16,
        "int8": jnp.int8,
        "f8": jnp.float8_e4m3fn,
    }[cfg.error_codec]


# ---------------------------------------------------------------------------
# convenience: full wire round trips used by the comm strategies
# ---------------------------------------------------------------------------

def compress(
    x: jax.Array, cfg: QuantConfig, key: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Flat f32 -> (packed int8 payload, f32 scales). Fixed mode returns
    a size-1 scales array (the static scale) so both modes share a wire shape.
    """
    if cfg.mode == "fixed":
        q = quant_fixed(x, cfg, key)
        scales = jnp.full((1,), cfg.scale, jnp.float32)
    elif cfg.mode == "tensor":
        q, scales = quant_tensor(x, cfg, key)
    else:
        q, scales = quant_block(x, cfg, key)
    if cfg.bits == 4:
        q = pack_int4(q)
    return q, scales


def decompress(payload: jax.Array, scales: jax.Array, cfg: QuantConfig) -> jax.Array:
    q = unpack_int4(payload) if cfg.bits == 4 else payload
    if cfg.mode in ("fixed", "tensor"):
        return q.astype(jnp.float32) / scales[0]
    return dequant_block(q, scales, cfg)


def roundtrip(x: jax.Array, cfg: QuantConfig, key: jax.Array | None = None) -> jax.Array:
    """deq(quant(x)) -- the lossy identity, used for error estimation."""
    payload, scales = compress(x, cfg, key)
    return decompress(payload, scales, cfg)
