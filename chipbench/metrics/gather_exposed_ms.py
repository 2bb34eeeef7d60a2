"""Device milliseconds per step in which an op of a forward weight
all-gather (``loco/gather``: the collective and the ops the chip runs it
with) runs and no compute op of another scope runs on that chip: the
gather's time left exposed, averaged over the cell's chips."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).exposed_ms("loco/gather")
