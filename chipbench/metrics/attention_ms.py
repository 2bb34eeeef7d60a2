"""Device milliseconds per step under ``model/attention``: the attention
block (pre-norm, QKV, RoPE, blockwise attention, output projection),
forward, backward and rematerialised, averaged over the cell's chips."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).ms({"model/attention"})
