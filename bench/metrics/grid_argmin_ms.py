"""Device time of the Pallas grid-argmin kernel per entry call, in ms.

Sums the durations of the kernel's operation events on every chip
(the table build runs on one) and divides by the traced calls.
"""

from harness import names


def read(ctx):
    tot, n = 0, 0
    for dev in ctx.trace.devices:
        for name, s, e in dev.ops:
            if names.GRID_ARGMIN_KERNEL in name:
                tot += e - s
                n += 1
    if n == 0:
        return None
    return tot / ctx.n_calls / 1e6
