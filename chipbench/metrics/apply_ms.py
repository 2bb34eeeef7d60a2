"""Device milliseconds per step under ``loco/apply``: the optimizer's
update of the parameters (``optim/optimizers.py`` via ``launch/steps.py``)."""


def read(ctx):
    ms = ctx["trace"].op_ms(lambda op: op.scope.startswith("loco/apply"))
    return ms / ctx["steps"] if ms else None
