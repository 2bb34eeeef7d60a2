"""Device milliseconds per step under ``model/head``: the final norm, the
logits matmul and scale and the cross-entropy, forward and backward,
averaged over the cell's chips."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).ms({"model/head"})
