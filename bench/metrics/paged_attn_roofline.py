"""Roofline share of the paged attention kernel
(``kernels/attention.py`` ``paged_prefill_attention``, which serves prompt
chunks and decode tokens alike): max(attention FLOPs / bf16 peak,
KV bytes / HBM bandwidth) of the steps in the window (``bench.flops``),
over the kernel's device time in the trace, in percent.  Layer: kernels.

The kernel's ``pallas_call`` carries no name of its own yet: the compiled
custom call takes the name of its jitted wrapper, and the TPU trace names
the event by its instruction, ``%paged_prefill_attention.<n> = ...
custom-call(...)``; ``PATTERN`` matches that."""
from bench.flops import attn_bytes, attn_flops
from bench.trace_reduce import op_seconds

PATTERN = r"^%?paged_prefill_attention\."


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    t = op_seconds(ctx.trace, PATTERN)
    if t <= 0:
        return None
    f = sum(attn_flops(ctx.cfg, s, n) for st in ctx.steps for s, n, _ in st)
    b = sum(attn_bytes(ctx.cfg, s, n) for st in ctx.steps for s, n, _ in st)
    return 100.0 * max(f / ctx.peak["bf16_flops"],
                       b / ctx.peak["hbm_bytes_per_s"]) / t
