"""The host spans of the entry calls (``core/spans.py``) in the profiler's
trace: one per phase as documented, none overlapping another, the chunk
loop's three in order, and nothing changed with the profiler off."""

import collections
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import composition as comp
from repro.core import controller as ctl
from repro.core import scenarios as scn
from repro.core import spans
from repro.core.accelerators import ACCELERATORS

# 300 steps in chunks of 128: two full chunks and a padded tail.
N_STEPS, CHUNK, N_CHUNKS = 300, 128, 3
SCENARIOS = ("burse", "diurnal")
STREAM_LOOP = (spans.STREAM_STAGE, spans.STREAM_DISPATCH, spans.STREAM_PULL)


def _platforms():
    return [ctl.fpga_platform(ACCELERATORS[n]) for n in ("tabla", "stripes")]


def _campaign():
    return scn.run_campaign(_platforms(), SCENARIOS, ("proposed", "freq_only"),
                            n_steps=N_STEPS, chunk_size=CHUNK)


def _composition():
    cand = np.array([[1, 0], [0, 2], [2, 1], [3, 3]])
    return comp.search_fleet_composition(_platforms(), cand, SCENARIOS,
                                         n_steps=N_STEPS, chunk_size=CHUNK)


#: entry -> (call, stream calls per entry call, entry.inputs spans per call)
ENTRIES = {"campaign": (_campaign, 1, 2), "composition": (_composition, 2, 4)}


def _program_spans(log_dir):
    """(name, start, end) of every program span in the trace, by start."""
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for p in data.planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name in spans.ALL), key=lambda ev: ev[1])


@pytest.fixture(scope="module", params=sorted(ENTRIES))
def traced(request, tmp_path_factory):
    """One entry call with the profiler off (after a warm-up), one with it
    on, one off again; the trace counters around the last two."""
    call, n_stream, n_inputs = ENTRIES[request.param]
    call()                                            # compiles
    off = call()
    before = ctl.fleet_trace_counts()
    log_dir = str(tmp_path_factory.mktemp("trace-" + request.param))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        on = call()
    off_again = call()
    return dict(events=_program_spans(log_dir), n_stream=n_stream,
                n_inputs=n_inputs, off=off, on=on, off_again=off_again,
                counts=(before, ctl.fleet_trace_counts()))


def test_one_span_per_phase(traced):
    n_stream = traced["n_stream"]
    got = collections.Counter(name for name, _, _ in traced["events"])
    assert got == {
        spans.ENTRY_INPUTS: traced["n_inputs"],
        spans.FLEET_TABLES: 1,
        spans.STREAM_SETUP: n_stream,
        spans.STREAM_STAGE: n_stream * N_CHUNKS,
        spans.STREAM_DISPATCH: n_stream * N_CHUNKS,
        spans.STREAM_PULL: n_stream * N_CHUNKS,
        spans.STREAM_FINISH: n_stream,
        spans.ENTRY_RESULTS: 1,
    }


def test_no_span_overlaps_another(traced):
    evs = traced["events"]
    for (a, _, a_end), (b, b_start, _) in zip(evs, evs[1:]):
        assert b_start >= a_end, (a, b)


def test_each_chunk_stages_dispatches_then_pulls(traced):
    stream = [name for name, _, _ in traced["events"]
              if name.startswith("fleet.stream.")]
    one_call = ([spans.STREAM_SETUP] + list(STREAM_LOOP) * N_CHUNKS
                + [spans.STREAM_FINISH])
    assert stream == one_call * traced["n_stream"]
    names = [name for name, _, _ in traced["events"]]
    assert names[0] == spans.ENTRY_INPUTS
    assert names[-1] == spans.ENTRY_RESULTS


def test_profiler_changes_no_result_and_no_trace_count(traced):
    before, after = traced["counts"]
    assert after == before
    want, want_def = jax.tree.flatten(traced["off"])
    for other in (traced["on"], traced["off_again"]):
        got, got_def = jax.tree.flatten(other)
        assert got_def == want_def
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)
