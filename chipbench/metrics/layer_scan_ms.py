"""Device milliseconds per step of the ops whose innermost scope is
``model/layers``: the layer scan's own work (stacking the carried
activations for the backward, the residual adds, the loop), outside the
attention and MLP blocks, averaged over the cell's chips."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).ms({"model/layers"})
