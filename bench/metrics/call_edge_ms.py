"""Host time of each entry call outside its device work, in ms.

Per traced call: the benchmark's span less the interval from the call's
first device operation to its last (suite stacking, availability
quantisation, table set-up on the host, result assembly, Pareto sets).
Averaged over the traced calls; on several chips the device interval
spans all of them.
"""


def read(ctx):
    tr = ctx.trace
    edges = []
    for lo, hi in tr.calls:
        starts, ends = [], []
        for dev in tr.devices:
            evs = [e for e in (dev.ops or dev.modules)
                   if e[1] >= lo and e[2] <= hi]
            if evs:
                starts.append(min(e[1] for e in evs))
                ends.append(max(e[2] for e in evs))
        if starts:
            edges.append((hi - lo) - (max(ends) - min(starts)))
    if not edges:
        return None
    return sum(edges) / len(edges) / 1e6
