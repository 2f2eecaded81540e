"""Mean device-idle gap between consecutive executions of the chunk
program inside one entry call, in us: the chunk's host staging, the
partial-sum pulls and the next dispatch.  Mean over chips."""

from harness import names, tracing


def read(ctx):
    gaps = []
    for dev in ctx.trace.devices:
        for lo, hi in ctx.trace.calls:
            mods = tracing.modules_named(dev, names.CHUNK_PROGRAM, lo, hi)
            gaps += [b[1] - a[2] for a, b in zip(mods, mods[1:])]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e3
