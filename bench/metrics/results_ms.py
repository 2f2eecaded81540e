"""Host time per entry call spent assembling results, in ms: the
program's ``entry.results`` span (result table or weighted sums, Pareto
sets), summed per traced call."""

from harness import spans


def read(ctx):
    return spans.per_call_ms(ctx, spans.ENTRY_RESULTS)
