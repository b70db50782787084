"""Device time per call of the jitted ``model_step`` (``LM.model_step``),
mean over its executions in the traced window, in ms.  Layer: model step."""
from bench.trace_reduce import module_times

PATTERN = r"model_step"


def read(ctx):
    if ctx.trace is None:
        return None
    times = module_times(ctx.trace, PATTERN)
    return 1e3 * sum(times) / len(times) if times else None
