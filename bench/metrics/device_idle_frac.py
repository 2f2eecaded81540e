"""Share of the traced window in which no operation ran on the device:
1 - (union of busy intervals / window), mean over chips."""

from harness import tracing


def read(ctx):
    tr = ctx.trace
    if not tr.devices or not tr.calls:
        return None
    lo, hi = tr.window()
    idle = [1.0 - tracing.busy_ns(d, lo, hi) / (hi - lo) for d in tr.devices]
    return sum(idle) / len(idle)
