"""Device milliseconds per step in which an op of the gradient exchange
(``loco/exchange``: its all-to-all, the ops the chip runs that collective
with, and the packing of the wire) runs and no compute op of another
scope runs on that chip: the exchange's time left exposed, averaged over
the cell's chips."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).exposed_ms("loco/exchange")
