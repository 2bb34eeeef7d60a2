"""Device milliseconds per step of the compute ops under ``loco/encode``
and ``loco/decode``: LoCo's compensate, quantize and pack, and the
dequantize and mean of what the peers sent, averaged over the chips."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).ms({"loco/encode", "loco/decode"},
                             compute_only=True)
