"""Model FLOPs per trained token, from the configuration alone.

Six FLOPs per matmul weight and token (forward 2, backward 4) over the
projections, the MLP and the output head (not the embedding lookup), plus
the attention products QK^T and PV: 4 * heads * head_dim FLOPs per key a
query attends to in the forward pass, three times that with the
backward, over the causal half within the window.  Recomputation
(rematerialized layers, attention over masked keys) is not counted.
"""
from __future__ import annotations

from chipbench.model import Dims


def mean_keys(seq_len: int, window: int | None) -> float:
    """Keys a query attends to, averaged over the positions of a row."""
    w = window or seq_len
    return sum(min(p + 1, w) for p in range(seq_len)) / seq_len


def per_token(dims: Dims, seq_len: int) -> float:
    attn = 12.0 * dims.heads * dims.hd * mean_keys(seq_len, dims.window)
    return 6.0 * dims.matmul_params() + dims.layers * attn


def codec_bytes_per_step(dims: Dims, wire, ranks: int, accum: int) -> float:
    """HBM bytes the LoCo codec must move per step and chip, from the
    parameter shapes alone (the same count for the jnp codec and the
    kernels).  For every tensor that carries error state, per microbatch:
    encode reads the f32 gradient and the f8 error and writes the f8 error
    and the int4 wire (6.5 bytes an element, as ``BENCH_kernels.json``
    counts fused loco4); decode reads the int4 wire it received (0.5 bytes
    an element) and writes the f32 mean of its own shard (4 bytes an
    element of the shard)."""
    from chipbench.reference import _compressed, _flat_items

    n = 0
    for name, shape in _flat_items(dims.shapes()).items():
        if _compressed(wire, name, shape):
            size = 1
            for s in shape:
                size *= s
            n += size
    return accum * (6.5 * n + 0.5 * n + 4.0 * n / ranks)
