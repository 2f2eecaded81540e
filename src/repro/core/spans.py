"""Named host spans of one entry call, for the profiler's trace.

An entry call (``scenarios.run_campaign``,
``composition.search_fleet_composition``) runs through the eight host
phases named below; each is wrapped in :func:`span`.  No span encloses
another, so within one call the time under a name adds up exactly and
any instant lies under at most one span.  Spans sit in host code only,
never inside a jitted function.  A span is a
``jax.profiler.TraceAnnotation``: it records only while the profiler
runs (``jax.profiler.trace``) and costs about a microsecond otherwise.
Each constant names the benchmark metric (``bench/metrics/``) that
reads it.
"""

import jax

#: Suite build and stacking, availability fractions and scales, per-candidate
#: tables and planes; one span before the table build and one after it, plus
#: one per composition half (``inputs_ms``).
ENTRY_INPUTS = "entry.inputs"
#: ``controller.fleet_bin_tables``: sweep rows, the grid-sweep program,
#: closed-form tables (``tables_host_ms``).
FLEET_TABLES = "fleet.tables"
#: ``simulate_fleet_stream`` up to its chunk loop: flatten, pad, predictor
#: state, sharding, the constant availability chunk (``stream_edges_ms``).
STREAM_SETUP = "fleet.stream.setup"
#: Per chunk: host slice and copy, transfer to the device, sharding
#: (``gap_stage_us``).
STREAM_STAGE = "fleet.stream.stage"
#: Per chunk: the call of the chunk program (``gap_dispatch_us``).
STREAM_DISPATCH = "fleet.stream.dispatch"
#: Per chunk: the wait for the chunk and the pulls of its partial sums and
#: emitted fields (``gap_pull_us``).
STREAM_PULL = "fleet.stream.pull"
#: After the loop: the final-state pulls and the summary
#: (``stream_edges_ms``).
STREAM_FINISH = "fleet.stream.finish"
#: Result assembly and Pareto sets (``results_ms``).
ENTRY_RESULTS = "entry.results"

ALL = (ENTRY_INPUTS, FLEET_TABLES, STREAM_SETUP, STREAM_STAGE,
       STREAM_DISPATCH, STREAM_PULL, STREAM_FINISH, ENTRY_RESULTS)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """Context manager that marks one host phase in the profiler's trace."""
    return jax.profiler.TraceAnnotation(name)
