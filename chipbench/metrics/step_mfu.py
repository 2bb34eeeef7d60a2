"""The whole step's share of the chip's bf16 peak over the traced steps,
in percent: model FLOPs per step (``chipbench.flops``) over the traced
window's length per step, chips and peak (``peaks.json``).  A kernel's
roofline that leaves the path goes silent; this share still bounds any
claim on ``tokens_per_s``."""
from chipbench import flops, peaks


def read(ctx):
    t = ctx["trace"]
    cell = ctx["cell"]
    if not t.window_s:
        return None
    work = cell.tokens_per_step * flops.per_token(ctx["dims"], cell.seq_len)
    peak = peaks.lookup(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * work / (t.window_s / ctx["steps"]) / (
        ctx["device"]["count"] * peak)
