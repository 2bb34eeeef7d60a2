"""Pallas kernel for the onebit wire: fused sign + 8-per-byte pack + error.

The onebit strategy (1-bit Adam lineage) ships one sign per element with a
per-segment L1 scale.  The unfused jnp path materializes the 0/1 mask, the
±scale reconstruction and the error update as separate f32-wide passes;
this kernel does sign-extract, LSB-first bit pack (per 256-element kernel
row, bit j of byte k = element 32j+k: eight contiguous 32-lane slices,
matching ``repro.core.quantizer.pack_signs``) and the
error-feedback update ``e_new = h - (2b-1)*scale`` in one pass, writing
1/8th byte per element of payload plus the bf16 error.

The L1 scale is a *global* mean over the segment, so it is computed outside
(one cheap reduction over ``h``) and enters the kernel as a (1, 1) scalar
operand mapped to every grid step.

``interpret`` is explicit, chosen by platform in ``kernels/ops.py``;
tests/test_kernels.py checks the kernel against its oracle in interpret
mode and tests/test_tpu_compile.py compiles it for a described v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.loco_quant import QBLOCK, _auto_rows

SIGN_PACK = 8  # signs per wire byte (= quantizer.SIGN_PACK)


def _sign_pack_kernel(h_ref, scale_ref, q_ref, enew_ref):
    h = h_ref[...].astype(jnp.float32)                  # (ROWS, QBLOCK)
    scale = scale_ref[0, 0]
    pos = h > 0
    d = jnp.where(pos, scale, -scale)
    enew_ref[...] = (h - d).astype(enew_ref.dtype)
    bits = pos.astype(jnp.int32)
    w = QBLOCK // SIGN_PACK
    packed = bits[:, :w]
    for j in range(1, SIGN_PACK):
        packed = packed | (bits[:, j * w:(j + 1) * w] << j)
    q_ref[...] = packed.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("state_dtype", "interpret", "rows"))
def onebit_pack(
    h: jax.Array,
    scale: jax.Array,
    *,
    state_dtype=jnp.bfloat16,
    interpret: bool,
    rows: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Compensated flat (n,) gradient + scalar L1 scale ->
    (packed signs (n//8,) uint8, e_new (n,) ``state_dtype``).

    n must be a multiple of 2*QBLOCK (FSDP padding guarantees 512-multiples).
    """
    n = h.shape[0]
    assert n % (2 * QBLOCK) == 0, n
    rows_total = n // QBLOCK
    R = rows or _auto_rows(rows_total)
    grid = (pl.cdiv(rows_total, R),)
    hm = h.astype(jnp.float32).reshape(rows_total, QBLOCK)
    sm = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    out_shapes = (
        jax.ShapeDtypeStruct((rows_total, QBLOCK // SIGN_PACK), jnp.uint8),
        jax.ShapeDtypeStruct((rows_total, QBLOCK), state_dtype),
    )
    packed, enew = pl.pallas_call(
        _sign_pack_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((R, QBLOCK // SIGN_PACK), lambda i: (i, 0)),
            pl.BlockSpec((R, QBLOCK), lambda i: (i, 0)),
        ),
        out_shape=out_shapes,
        interpret=interpret,
        name="onebit_sign_pack",
    )(hm, sm)
    return packed.reshape(n // SIGN_PACK), enew.reshape(n)
