"""Device milliseconds per step under ``model/embed``: the token gather
and its transposed scatter into the embedding's gradient, averaged over
the cell's chips."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).ms({"model/embed"})
