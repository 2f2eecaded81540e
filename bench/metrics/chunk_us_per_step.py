"""Device time of the chunk program per scan step dispatched, in us.

Each execution of the chunk program scans C steps of all the cells on
its chip; the metric is the program's total device time over the
traced calls divided by (executions x C), averaged over chips.
"""

from harness import names, tracing


def read(ctx):
    per_dev = []
    for dev in ctx.trace.devices:
        mods = [m for lo, hi in ctx.trace.calls
                for m in tracing.modules_named(dev, names.CHUNK_PROGRAM,
                                               lo, hi)]
        if mods:
            busy = sum(e - s for _, s, e in mods)
            per_dev.append(busy / (len(mods) * ctx.chunk_size))
    if not per_dev:
        return None
    return sum(per_dev) / len(per_dev) / 1e3
