"""The readers of the program's host spans (``harness/spans.py`` and the
seven ``metrics/`` files that use it).

A hand-made trace whose answers are known, the chip fixture recorded
before the program had spans (every reader silent), and the names
against the program's own.
"""

import importlib.util
import lzma
import os

import pytest

from harness import names, spans, tracing

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PER_CALL = ("inputs_ms", "tables_host_ms", "stream_edges_ms", "results_ms")
GAPS = ("gap_stage_us", "gap_dispatch_us", "gap_pull_us")
FIXTURE = os.path.join(BENCH, "tests", "data", "aggregate-2048.xplane.pb.xz")
MS = 1_000_000


def _read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        "m_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


class Ctx:
    def __init__(self, trace, n_calls, chunk_size):
        self.trace, self.n_calls, self.chunk_size = trace, n_calls, chunk_size


def _hand_made():
    """Two calls of 100 ms, each with two chunk-program executions per
    chip.  Chip 0 runs them at +18..+38 and +43..+63 ms (a 5 ms gap), chip
    1 at +18..+38 and +45..+65 ms (7 ms).  Host spans per call, in ms:
    inputs 0-8, tables 8-12, inputs 12-14, setup 14-16, stage 16-17,
    dispatch 17-18, pull 18-39, stage 39-41, dispatch 41-42, pull 42-66,
    finish 66-68, results 68-72.  One more inputs span lies outside any
    call, and one host event is no program span."""
    chunk = "jit_" + names.CHUNK_PROGRAM
    host = [("entry.inputs", 150 * MS, 160 * MS),
            ("PjitFunction(broadcast_in_dim)", 1 * MS, 2 * MS)]
    dev0, dev1 = [], []
    for t in (0, 200 * MS):
        host += [(n, t + a * MS, t + b * MS) for n, a, b in (
            (spans.ENTRY_INPUTS, 0, 8), (spans.FLEET_TABLES, 8, 12),
            (spans.ENTRY_INPUTS, 12, 14), (spans.STREAM_SETUP, 14, 16),
            (spans.STREAM_STAGE, 16, 17), (spans.STREAM_DISPATCH, 17, 18),
            (spans.STREAM_PULL, 18, 39), (spans.STREAM_STAGE, 39, 41),
            (spans.STREAM_DISPATCH, 41, 42), (spans.STREAM_PULL, 42, 66),
            (spans.STREAM_FINISH, 66, 68), (spans.ENTRY_RESULTS, 68, 72))]
        dev0 += [(chunk, t + 18 * MS, t + 38 * MS),
                 (chunk, t + 43 * MS, t + 63 * MS)]
        dev1 += [(chunk, t + 18 * MS, t + 38 * MS),
                 (chunk, t + 45 * MS, t + 65 * MS)]
    devs = [tracing.Device("/device:TPU:0", dev0, []),
            tracing.Device("/device:TPU:1", dev1, [])]
    return tracing.Trace(devs, host, [(0, 100 * MS), (200 * MS, 300 * MS)])


def test_hand_made_trace():
    ctx = Ctx(_hand_made(), n_calls=2, chunk_size=512)
    got = {m: _read(m, ctx) for m in PER_CALL + GAPS + ("chunk_gap_us",)}
    assert got == pytest.approx({
        "inputs_ms": 8 + 2, "tables_host_ms": 4, "stream_edges_ms": 2 + 2,
        "results_ms": 4,
        # gaps 38-43 (chip 0) and 38-45 (chip 1): pull 38-39, stage 39-41,
        # dispatch 41-42, pull 42-43 / 42-45
        "gap_stage_us": 2000, "gap_dispatch_us": 1000,
        "gap_pull_us": (2000 + 4000) / 2, "chunk_gap_us": (5000 + 7000) / 2})


def test_gaps_add_up_to_no_more_than_the_chunk_gap():
    tr = _hand_made()
    ctx = Ctx(tr, n_calls=2, chunk_size=512)
    whole = _read("chunk_gap_us", ctx)
    assert sum(_read(m, ctx) for m in GAPS) == pytest.approx(whole)
    # without its dispatch spans, 1 ms of each gap lies under no span
    tr.host = [ev for ev in tr.host if ev[0] != spans.STREAM_DISPATCH]
    assert _read("gap_dispatch_us", ctx) is None
    parts = sum(_read(m, ctx) for m in ("gap_stage_us", "gap_pull_us"))
    assert parts == pytest.approx(whole - 1000)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with lzma.open(FIXTURE) as f:
        path.write_bytes(f.read())
    return tracing.load(str(path))


@pytest.mark.parametrize("name", PER_CALL + GAPS)
def test_reader_is_silent_on_a_trace_without_spans(recorded, name):
    """The chip fixture was recorded before the program had spans."""
    assert len(recorded.calls) == 3
    assert _read(name, Ctx(recorded, n_calls=3, chunk_size=1024)) is None


def test_names_match_the_program():
    from repro.core import spans as program
    assert spans.ALL == program.ALL
    for const in ("ENTRY_INPUTS", "FLEET_TABLES", "STREAM_SETUP",
                  "STREAM_STAGE", "STREAM_DISPATCH", "STREAM_PULL",
                  "STREAM_FINISH", "ENTRY_RESULTS"):
        assert getattr(spans, const) == getattr(program, const), const
