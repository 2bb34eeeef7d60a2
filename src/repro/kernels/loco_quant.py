"""Pallas TPU kernels for the quantized-wire compression hot path.

Two kernel families cover the per-step elementwise work that LoCo-style
sync adds on top of the optimizer (paper §3.1-§3.2).  On an A100 the
reference does this with fused CUDA ops; on TPU we tile the flat gradient
into VMEM-resident (ROWS, 256) blocks (256 = quantizer block = 2 VREG
lanes of 128) and fuse:

* ``fused_compress``: error-decode + compensate + per-block absmax
  quantize (4- or 8-bit) + nibble-pack (the half-split layout of
  ``quantizer.pack_int4``: one kernel row is one packing granule, its two
  128-lane halves fill the low and high nibbles) + error update + error
  encode
  -- one pass over the gradient, one pass out for payload/scales/error.
  Parameterized by ``bits`` (4: nibble-packed int4, 8: int8) and ``err``
  (``"f8"``: LoCo's scaled f8_e4m3 storage with ±448 saturation;
  ``"bf16"``: EF's unscaled bf16 storage).  ``loco_compress`` /
  ``ef_compress`` are the named specializations the fast-path registry
  mounts (see repro.core.codec).
* ``dequant_mean``: (nibble-unpack +) dequant + mean over the D peer
  contributions received from the all-to-all -- one pass over the received
  buffer, shared by the loco/ef/naive4 decode side.

Weak spots the MXU can't help with (this is pure VPU work); the win is
fusion: the unfused jnp path reads/writes the f32 gradient ~6x.

Every entry point takes ``interpret`` explicitly: ``kernels/ops.py``
chooses it from the platform (compiled on TPU, interpreted elsewhere).
tests/test_kernels.py checks each kernel against its oracle in interpret
mode; tests/test_tpu_compile.py compiles each for a described v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QBLOCK = 256          # quantizer block (elements per scale)
ROWS = 64             # rows of QBLOCK per pallas block -> 16K elems in VMEM
F8_MAX = 448.0        # float8_e4m3fn saturation bound


# ---------------------------------------------------------------------------
# kernel 1: fused compensate + quantize(block absmax) + pack + err update
# ---------------------------------------------------------------------------

def _compress_kernel(g_ref, e_ref, q_ref, s_ref, enew_ref, *,
                     bits: int, beta: float, escale: float, err: str):
    g = g_ref[...].astype(jnp.float32)                  # (ROWS, QBLOCK)
    if err == "f8":
        e = e_ref[...].astype(jnp.float32) / escale     # decompressor(e; s_e)
    else:  # "bf16": unscaled float storage (EF)
        e = e_ref[...].astype(jnp.float32)
    h = g + e                                           # Eqn. (2)
    qmax = float(2 ** (bits - 1) - 1)
    qmin = float(-(2 ** (bits - 1)))
    absmax = jnp.max(jnp.abs(h), axis=1, keepdims=True)
    scale = qmax / jnp.maximum(absmax, 1e-30)
    q = jnp.clip(jnp.round(h * scale), qmin, qmax)      # Eqn. (3)
    d = q / scale                                       # decompressor(q; s)
    e_tilde = (1.0 - beta) * e + beta * (h - d)         # Eqn. (5)
    if err == "f8":
        enew = jnp.clip(e_tilde * escale, -F8_MAX, F8_MAX)
    else:
        enew = e_tilde
    enew_ref[...] = enew.astype(enew_ref.dtype)
    s_ref[...] = scale[:, :1]
    qi = q.astype(jnp.int32)
    if bits == 4:  # half-split nibbles (quantizer.pack_int4), int32 shifts
        half = QBLOCK // 2
        lo, hi = qi[:, :half], qi[:, half:]
        q_ref[...] = ((hi << 4) | (lo & 0xF)).astype(jnp.int8)
    else:
        q_ref[...] = qi.astype(jnp.int8)


def _auto_rows(rows_total: int, max_rows: int = ROWS) -> int:
    """Row block: ``max_rows`` (a multiple of 32, the sublane tile of the
    8-bit operands every kernel here has), or the whole array when it is
    shorter.  The grid is ``cdiv(rows_total, R)``: a ragged last block is
    padded on read and masked on write, and every kernel is row-local."""
    return min(rows_total, max_rows)


@functools.partial(jax.jit, static_argnames=("bits", "beta", "escale", "err",
                                             "interpret", "rows"))
def fused_compress(
    g: jax.Array,
    e: jax.Array,
    *,
    bits: int = 4,
    beta: float,
    escale: float,
    err: str = "f8",
    interpret: bool,
    rows: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flat (n,) gradient + (n,) error -> (payload, scales (n//QBLOCK,), e_new (n,)).

    payload is (n//2,) nibble-packed int8 at 4 bits, (n,) int8 at 8 bits;
    e_new keeps the input error dtype (f8_e4m3 for ``err="f8"``, bf16 for
    ``err="bf16"``).  n must be a multiple of 2*QBLOCK (the FSDP padding
    guarantees multiples of 512).
    """
    n = g.shape[0]
    assert bits in (4, 8), bits
    assert err in ("f8", "bf16"), err
    assert n % (2 * QBLOCK) == 0, n
    rows_total = n // QBLOCK
    R = rows or _auto_rows(rows_total)
    grid = (pl.cdiv(rows_total, R),)
    pay_cols = QBLOCK // 2 if bits == 4 else QBLOCK
    gm = g.reshape(rows_total, QBLOCK)
    em = e.reshape(rows_total, QBLOCK)
    out_shapes = (
        jax.ShapeDtypeStruct((rows_total, pay_cols), jnp.int8),
        jax.ShapeDtypeStruct((rows_total, 1), jnp.float32),
        jax.ShapeDtypeStruct((rows_total, QBLOCK), e.dtype),
    )
    q, s, enew = pl.pallas_call(
        functools.partial(_compress_kernel, bits=bits, beta=beta,
                          escale=escale, err=err),
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((R, QBLOCK), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((R, pay_cols), lambda i: (i, 0)),
            pl.BlockSpec((R, 1), lambda i: (i, 0)),
            pl.BlockSpec((R, QBLOCK), lambda i: (i, 0)),
        ),
        out_shape=out_shapes,
        interpret=interpret,
        name="loco_fused_compress",
    )(gm, em)
    return q.reshape(-1), s.reshape(n // QBLOCK), enew.reshape(n)


def loco_compress(g, e8, *, beta: float, escale: float, bits: int = 4,
                  interpret: bool, rows: int | None = None):
    """LoCo specialization: f8 error storage, moving-average update."""
    return fused_compress(g, e8, bits=bits, beta=beta, escale=escale,
                          err="f8", interpret=interpret, rows=rows)


def ef_compress(g, e, *, bits: int = 4, interpret: bool,
                rows: int | None = None):
    """EF specialization: beta=1 (full last-step error), bf16 storage."""
    return fused_compress(g, e, bits=bits, beta=1.0, escale=1.0,
                          err="bf16", interpret=interpret, rows=rows)


# ---------------------------------------------------------------------------
# kernel 2: unpack + dequant + mean over peers
# ---------------------------------------------------------------------------

def _dequant_mean_kernel(q_ref, s_ref, out_ref, *, bits: int):
    q = q_ref[...]                                      # (D, ROWS, pay_cols) int8
    s = s_ref[...]                                      # (D, ROWS, 1) f32
    if bits == 4:  # half-split nibbles (quantizer.unpack_int4), int32 shifts
        b = q.astype(jnp.int32)
        lo = (((b & 0xF) ^ 8) - 8).astype(jnp.float32)
        hi = (b >> 4).astype(jnp.float32)
        half = QBLOCK // 2
        out_ref[:, :half] = jnp.mean(lo / s, axis=0)
        out_ref[:, half:] = jnp.mean(hi / s, axis=0)
    else:
        out_ref[...] = jnp.mean(q.astype(jnp.float32) / s, axis=0)


@functools.partial(jax.jit, static_argnames=("bits", "interpret", "rows"))
def dequant_mean(
    payload: jax.Array,  # (D, m) int8, m = n/D/2 at 4 bits else n/D
    scales: jax.Array,   # (D, n/D/QBLOCK) f32
    *,
    bits: int = 4,
    interpret: bool,
    rows: int | None = None,
) -> jax.Array:
    """Received all-to-all rows -> fp32 mean gradient chunk (n/D,)."""
    assert bits in (4, 8), bits
    D, m = payload.shape
    n_chunk = m * 2 if bits == 4 else m
    assert n_chunk % (2 * QBLOCK) == 0, n_chunk
    rows_total = n_chunk // QBLOCK
    R = rows or _auto_rows(rows_total)
    grid = (pl.cdiv(rows_total, R),)
    pay_cols = QBLOCK // 2 if bits == 4 else QBLOCK
    pm = payload.reshape(D, rows_total, pay_cols)
    sm = scales.reshape(D, rows_total, 1)
    out = pl.pallas_call(
        functools.partial(_dequant_mean_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((D, R, pay_cols), lambda i: (0, i, 0)),
            pl.BlockSpec((D, R, 1), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((R, QBLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_total, QBLOCK), jnp.float32),
        interpret=interpret,
        name="loco_dequant_mean",
    )(pm, sm)
    return out.reshape(n_chunk)
