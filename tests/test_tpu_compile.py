"""Every Pallas kernel cell compiles for a described TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib, so it can compile for a chip
that is described rather than attached.  Interpret mode (tests/test_kernels.py)
cannot see what Mosaic refuses -- strided lane slices, casts it cannot
legalize, row blocks off the (8|32, 128) tiling -- so each cell is
compiled here at a real width (n = 2^22, D = 4 peers) with
``interpret=False``, plus short and ragged segments that pin the row-block
choice.  Nothing runs: the compile alone is the check.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every xdist worker imports
this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import act_quant as AQ
from repro.kernels import loco_quant as LQ
from repro.kernels import sign_pack as SP

N = 1 << 22      # flat gradient elements (a 16 MiB f32 segment)
D = 4            # peers of the all-to-all (one v5e 2x2 host)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compress(bits, err, n):
    f = functools.partial(LQ.fused_compress, bits=bits, err=err,
                          beta=0.5 if err == "f8" else 1.0,
                          escale=2.0**14 if err == "f8" else 1.0,
                          interpret=False)
    edt = jnp.float8_e4m3fn if err == "f8" else jnp.bfloat16
    return f, [((n,), jnp.float32), ((n,), edt)]


def _dequant(bits, n):
    f = functools.partial(LQ.dequant_mean, bits=bits, interpret=False)
    m = n // D // 2 if bits == 4 else n // D
    return f, [((D, m), jnp.int8), ((D, n // D // LQ.QBLOCK), jnp.float32)]


def _onebit(n):
    f = functools.partial(SP.onebit_pack, interpret=False)
    return f, [((n,), jnp.float32), ((), jnp.float32)]


def _act_encode(rows):
    f = functools.partial(AQ.act_encode, interpret=False)
    return f, [((rows, AQ.ACT_BLOCK), jnp.float32)]


def _act_decode(rows):
    f = functools.partial(AQ.act_decode, interpret=False)
    return f, [((rows, AQ.ACT_BLOCK), jnp.int8), ((rows,), jnp.float32)]


# 1536 elements = 6 kernel rows (whole-array block); 130 * 256 = 33280
# elements = 130 rows (two full 64-row blocks and a ragged third)
CELLS = {
    "loco4": lambda: _compress(4, "f8", N),
    "loco8": lambda: _compress(8, "f8", N),
    "ef4": lambda: _compress(4, "bf16", N),
    "ef8": lambda: _compress(8, "bf16", N),
    "dequant_mean4": lambda: _dequant(4, N),
    "dequant_mean8": lambda: _dequant(8, N),
    "onebit_pack": lambda: _onebit(N),
    "act_encode": lambda: _act_encode(N // AQ.ACT_BLOCK),
    "act_decode": lambda: _act_decode(N // AQ.ACT_BLOCK),
    "loco4_small_1536": lambda: _compress(4, "f8", 1536),
    "dequant_mean4_small_1536": lambda: _dequant(4, 1536 * D),
    "onebit_pack_small_1536": lambda: _onebit(1536),
    "loco4_ragged_130_rows": lambda: _compress(4, "f8", 130 * LQ.QBLOCK),
    "act_encode_ragged_45_rows": lambda: _act_encode(45),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_kernel_compiles_for_v5e(one_chip, cell):
    fn, specs = CELLS[cell]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in specs]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo, cell
