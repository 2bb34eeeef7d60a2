"""jit'd public wrappers for the Pallas kernels + fast-path registration.

Interpret mode is chosen here, by platform, and nowhere else: on TPU the
BlockSpec tiling compiles to Mosaic; on any other backend the kernel body
runs in the Pallas interpreter, which is how the CPU tests check it.

Importing this module registers every fused fast path with the codec
registry (``repro.core.codec.register_fastpath``); the codec layer imports
it lazily on first dispatch, so ``SyncConfig.use_kernels`` routes through
here without core->kernels import cycles.  Coverage (see EXPERIMENTS.md
§Kernels for the full table):

=========================================  ==============  ===============
registry key                               encode          decode_mean
=========================================  ==============  ===============
(loco,   4, block, f8)                     fused_compress  dequant_mean
(loco,   8, block, f8)                     fused_compress  dequant_mean
(ef,     4, block, bf16)                   fused_compress  dequant_mean
(ef,     8, block, bf16)                   fused_compress  dequant_mean
(naive4, 4, block, none)                   --  (jnp)       dequant_mean
(naive4, 8, block, none)                   --  (jnp)       dequant_mean
(onebit, 1, l1,    bf16)                   onebit_pack     --  (jnp)
=========================================  ==============  ===============

The MoE activation-wire cell (``act_quant``) is not registry-keyed: it is
stateless and layout-fixed, so ``core/act_comm`` calls ``act_encode`` /
``act_decode`` directly when ``REPRO_ACT_KERNELS=1`` (jnp reference
otherwise; parity pinned by tests/test_act_comm.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import codec as codec_lib
from repro.kernels import loco_quant, sign_pack


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def loco_compress(g, e8, *, beta: float, escale: float, bits: int = 4):
    """Fused compensate+quant+pack+error-update (see loco_quant)."""
    return loco_quant.loco_compress(
        g, e8, beta=beta, escale=escale, bits=bits,
        interpret=_interpret_default()
    )


def ef_compress(g, e, *, bits: int = 4):
    """Fused EF compensate+quant+pack with bf16 error storage."""
    return loco_quant.ef_compress(g, e, bits=bits,
                                  interpret=_interpret_default())


def dequant_mean(payload, scales, *, bits: int = 4):
    """Fused unpack+dequant+mean over the received all-to-all rows."""
    return loco_quant.dequant_mean(payload, scales, bits=bits,
                                   interpret=_interpret_default())


def onebit_pack(h, scale, *, state_dtype=jnp.bfloat16):
    """Fused sign-extract + 8-per-byte pack + error update."""
    return sign_pack.onebit_pack(h, scale, state_dtype=state_dtype,
                                 interpret=_interpret_default())


def act_encode(h):
    """MoE activation-wire block quantize (see act_quant / core.act_comm)."""
    from repro.kernels import act_quant
    return act_quant.act_encode(h, interpret=_interpret_default())


def act_decode(q, scale):
    """MoE activation-wire block dequantize."""
    from repro.kernels import act_quant
    return act_quant.act_decode(q, scale, interpret=_interpret_default())


# ---------------------------------------------------------------------------
# fast-path registration (adapters from kernel tuples to codec wire pytrees)
# ---------------------------------------------------------------------------

def _quant_encode(cfg, g, state):
    qc = cfg.quant
    if cfg.strategy == "loco":
        q, s, enew = loco_compress(g.astype(jnp.float32), state,
                                   beta=cfg.beta, escale=qc.error_scale,
                                   bits=qc.bits)
    else:  # ef
        q, s, enew = ef_compress(g.astype(jnp.float32), state, bits=qc.bits)
    return {"payload": q, "scales": s}, enew


def _quant_decode_mean(cfg, recv):
    return dequant_mean(recv["payload"], recv["scales"], bits=cfg.quant.bits)


def _onebit_encode(cfg, g, state):
    h = g.astype(jnp.float32) + state.astype(jnp.float32)
    scale = jnp.mean(jnp.abs(h))
    packed, enew = onebit_pack(h, scale, state_dtype=state.dtype)
    return {"payload": packed, "scales": scale.reshape(1)}, enew


for _bits in (4, 8):
    codec_lib.register_fastpath(("loco", _bits, "block", "f8"),
                                encode=_quant_encode,
                                decode_mean=_quant_decode_mean)
    codec_lib.register_fastpath(("ef", _bits, "block", "bf16"),
                                encode=_quant_encode,
                                decode_mean=_quant_decode_mean)
    codec_lib.register_fastpath(("naive4", _bits, "block", "none"),
                                decode_mean=_quant_decode_mean)
codec_lib.register_fastpath(("onebit", 1, "l1", "bf16"),
                            encode=_onebit_encode)
