"""Device milliseconds per step of the compute operations outside every
``loco/`` scope: the model's forward and backward, the loss, the gradient
accumulation and clipping (not the collectives)."""


def read(ctx):
    t = ctx["trace"]
    ms = t.op_ms(lambda op: not op.collective and not op.scope.startswith("loco/"))
    return ms / ctx["steps"] if ms else None
