"""The program's host spans (``repro.core.spans``) and the two reductions
the span metrics share.

The names are literals: the benchmark never imports the program.  The
program's spans never nest, so time under a name adds up exactly and an
instant lies under at most one span.  A trace recorded before the spans
existed holds none of them, and every reader then returns ``None``.
"""

from __future__ import annotations

from typing import Optional

from harness import names, tracing

ENTRY_INPUTS = "entry.inputs"
FLEET_TABLES = "fleet.tables"
STREAM_SETUP = "fleet.stream.setup"
STREAM_STAGE = "fleet.stream.stage"
STREAM_DISPATCH = "fleet.stream.dispatch"
STREAM_PULL = "fleet.stream.pull"
STREAM_FINISH = "fleet.stream.finish"
ENTRY_RESULTS = "entry.results"

ALL = (ENTRY_INPUTS, FLEET_TABLES, STREAM_SETUP, STREAM_STAGE,
       STREAM_DISPATCH, STREAM_PULL, STREAM_FINISH, ENTRY_RESULTS)


def per_call_ms(ctx, *span_names: str) -> Optional[float]:
    """Summed durations of the named spans that start inside a traced
    entry call, divided by the number of traced calls, in ms."""
    calls = ctx.trace.calls
    inside = [e - s for n, s, e in ctx.trace.host
              if n in span_names and any(lo <= s < hi for lo, hi in calls)]
    if not inside:
        return None
    return sum(inside) / len(calls) / 1e6


def gap_us(ctx, span_name: str) -> Optional[float]:
    """Mean time, in us, that the named span covers of each device gap
    between consecutive chunk-program executions inside one traced call
    (the gaps ``chunk_gap_us`` averages, per chip)."""
    under = tracing.union([ev for ev in ctx.trace.host if ev[0] == span_name])
    if not under:
        return None
    gaps = []
    for dev in ctx.trace.devices:
        for lo, hi in ctx.trace.calls:
            mods = tracing.modules_named(dev, names.CHUNK_PROGRAM, lo, hi)
            gaps += [(a[2], b[1]) for a, b in zip(mods, mods[1:])]
    if not gaps:
        return None
    owned = [sum(max(0, min(e, g1) - max(s, g0)) for s, e in under)
             for g0, g1 in gaps]
    return sum(owned) / len(gaps) / 1e3
