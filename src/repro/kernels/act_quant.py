"""Pallas encode/decode cell for the MoE activation wire (core/act_comm).

One kernel pair over the ``(rows, ACT_BLOCK)`` layout the activation
exchange quantizes -- the activation-shaped sibling of
``loco_quant.fused_compress``/``dequant_mean`` (same VPU tiling discipline:
VMEM-resident row blocks, one pass in, one pass out), but stateless: no
error term, no peer mean, just per-512-block absmax int8 both ways.

ACT_BLOCK is 512 (= the wire granule of core/act_comm, 4 VREG lanes of
128), so a pallas row block of 32 rows is 16K elements in VMEM -- the same
budget loco_quant uses at (64, 256).

Like every kernel in this package the cell takes ``interpret`` explicitly
(``kernels/ops.py`` chooses it by platform); core/act_comm keeps a jnp
reference as the default path and routes here only when
``REPRO_ACT_KERNELS=1`` -- parity is pinned by tests/test_act_comm.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.loco_quant import _auto_rows

ACT_BLOCK = 512
QMAX = 127.0
ROWS = 32         # rows of ACT_BLOCK per pallas block (multiple of 32: int8)


def _encode_kernel(h_ref, q_ref, s_ref):
    h = h_ref[...].astype(jnp.float32)                  # (R, ACT_BLOCK)
    absmax = jnp.max(jnp.abs(h), axis=1, keepdims=True)
    scale = QMAX / jnp.maximum(absmax, 1e-30)
    q_ref[...] = jnp.clip(jnp.round(h * scale), -128, 127).astype(jnp.int8)
    s_ref[...] = scale


def _decode_kernel(q_ref, s_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) / s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "rows"))
def act_encode(h: jax.Array, *, interpret: bool,
               rows: int | None = None) -> tuple[jax.Array, jax.Array]:
    """``(rows, ACT_BLOCK)`` f32 -> (int8 codes, f32 scales ``(rows,)``)."""
    rows_total, blk = h.shape
    assert blk == ACT_BLOCK, h.shape
    R = rows or _auto_rows(rows_total, ROWS)
    q, s = pl.pallas_call(
        _encode_kernel,
        grid=(pl.cdiv(rows_total, R),),
        in_specs=[pl.BlockSpec((R, ACT_BLOCK), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((R, ACT_BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((R, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rows_total, ACT_BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((rows_total, 1), jnp.float32),
        ),
        interpret=interpret,
        name="act_quant_encode",
    )(h)
    return q, s.reshape(rows_total)


@functools.partial(jax.jit, static_argnames=("interpret", "rows"))
def act_decode(q: jax.Array, scale: jax.Array, *, interpret: bool,
               rows: int | None = None) -> jax.Array:
    """(int8 codes, scales) -> ``(rows, ACT_BLOCK)`` f32."""
    rows_total, blk = q.shape
    assert blk == ACT_BLOCK, q.shape
    R = rows or _auto_rows(rows_total, ROWS)
    return pl.pallas_call(
        _decode_kernel,
        grid=(pl.cdiv(rows_total, R),),
        in_specs=[
            pl.BlockSpec((R, ACT_BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((R, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((R, ACT_BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_total, ACT_BLOCK), jnp.float32),
        interpret=interpret,
        name="act_quant_decode",
    )(q, scale.reshape(rows_total, 1))
