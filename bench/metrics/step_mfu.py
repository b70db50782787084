"""Model FLOPs of every step dispatched in the window (matmuls,
attention by context, unembed only for sampled rows; ``bench.flops``
with the cell's architecture module, ``ctx.arch``) per second of the
window, over the chip's bf16 peak, in percent.  Layer: the whole step."""
from bench.flops import step_work


def read(ctx):
    if not ctx.steps:
        return None
    flops = sum(step_work(ctx.arch, ctx.cfg, s, ctx.weight_bytes)[
        "model_flops"] for s in ctx.steps)
    return 100.0 * flops / (ctx.t1 - ctx.t0) / ctx.peak["bf16_flops"]
