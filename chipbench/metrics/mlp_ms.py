"""Device milliseconds per step under ``model/mlp``: the MLP block
(pre-norm, gate, up and down projections; the routed experts of a MoE
arch), forward, backward and rematerialised, averaged over the chips."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).ms({"model/mlp"})
