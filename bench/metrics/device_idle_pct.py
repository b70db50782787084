"""Device idle share: 1 - (union of device-op intervals / traced window),
from the profiler trace, in percent.  Layer: device."""


def read(ctx):
    red = ctx.trace
    if red is None or not red["chips"] or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
