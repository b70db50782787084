"""Roofline share of the paged attention kernel
(``kernels/attention.py`` ``paged_prefill_attention``, which serves prompt
chunks and decode tokens alike): max(attention FLOPs / bf16 peak,
KV bytes / HBM bandwidth) of the steps in the window
(``bench.flops.attn_work`` over the layers of the cell's architecture
module, ``ctx.arch``), over the kernel's device time in the trace, in
percent.  Layer: kernels.

The kernel's ``pallas_call`` is named ``paged_prefill_attention`` (the
Mosaic kernel's name); the TPU trace names its event by the HLO
instruction, ``%paged_prefill_attention.<n> = ... custom-call(...)``,
which ``PATTERN`` matches."""
from bench.flops import attn_work
from bench.trace_reduce import op_seconds

PATTERN = r"^%?paged_prefill_attention\."


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    t = op_seconds(ctx.trace, PATTERN)
    if t <= 0:
        return None
    f, b = attn_work(ctx.arch, ctx.cfg,
                     [e for st in ctx.steps for e in st])
    return 100.0 * max(f / ctx.peak["bf16_flops"],
                       b / ctx.peak["hbm_bytes_per_s"]) / t
