"""The reduction from a profiler trace to the per-layer metrics.

Two checks: a hand-made trace whose answers are known, and a small trace
recorded on a TPU v5 lite chip (``data/``, a traced run of
``fpga5-paper.aggregate`` cut to 2,048 steps) whose readings are pinned.
"""

import importlib.util
import os

import pytest

from harness import names, tracing

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS = ("call_edge_ms", "grid_argmin_ms", "chunk_gap_us",
           "chunk_us_per_step", "device_idle_frac")
FIXTURE = os.path.join(BENCH, "tests", "data", "aggregate-2048.xplane.pb.xz")


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
    ms = 1_000_000
    chunk = names.CHUNK_PROGRAM
    # Two calls of 100 ms.  In each: the tables program (with the kernel)
    # at +10..+12 ms, then two chunk programs of 20 ms with a 5 ms gap,
    # the device work ending at +57 ms.
    mods, ops = [], []
    for c in (0, 200):
        t = c * ms
        mods += [("jit_" + names.TABLES_PROGRAM, t + 10 * ms, t + 12 * ms),
                 ("jit_" + chunk, t + 12 * ms, t + 32 * ms),
                 ("jit_" + chunk, t + 37 * ms, t + 57 * ms)]
        ops += [(names.GRID_ARGMIN_KERNEL, t + 10 * ms, t + 11 * ms),
                ("fusion", t + 11 * ms, t + 12 * ms),
                ("while", t + 12 * ms, t + 32 * ms),
                ("while", t + 37 * ms, t + 57 * ms)]
    dev = tracing.Device("/device:TPU:0", mods, ops)
    calls = [(0, 100 * ms), (200 * ms, 300 * ms)]
    host = [("stage", 32 * ms, 37 * ms)]
    return tracing.Trace([dev], host, calls)


def test_hand_made_trace():
    tr = _hand_made()
    ctx = Ctx(tr, n_calls=2, chunk_size=1000)
    got = {m: _read(m, ctx) for m in METRICS}
    assert got["call_edge_ms"] == pytest.approx(100 - 47)
    assert got["grid_argmin_ms"] == pytest.approx(1.0)
    assert got["chunk_gap_us"] == pytest.approx(5000.0)
    assert got["chunk_us_per_step"] == pytest.approx(20.0)
    # busy 47 - 5 = 42 ms of each call; window 300 ms
    assert got["device_idle_frac"] == pytest.approx(1 - 84 / 300)
    gaps = tracing.idle_gaps(tr)
    assert gaps[0][1] == pytest.approx(0.153)      # 57 ms .. 210 ms
    assert ["host: stage", 0.005] in [[n, round(v, 6)] for n, v in gaps]


def test_metric_is_silent_without_its_events():
    tr = tracing.Trace([tracing.Device("/device:TPU:0", [], [])], [],
                       [(0, 10)])
    ctx = Ctx(tr, n_calls=1, chunk_size=8)
    for m in ("grid_argmin_ms", "chunk_gap_us", "chunk_us_per_step",
              "call_edge_ms"):
        assert _read(m, ctx) is None, m


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import lzma
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with lzma.open(FIXTURE) as f:
        path.write_bytes(f.read())
    return tracing.load(str(path))


def test_recorded_chip_trace(recorded):
    """Three traced calls of 450 cells x 2,048 steps (two chunk-program
    executions each) on one TPU v5 lite chip; readings pinned as first
    read, so a change to the reduction shows."""
    tr = recorded
    assert len(tr.calls) == 3 and len(tr.devices) == 1
    lo, hi = tr.window()
    for a, b in tr.calls:
        assert len(tracing.modules_named(tr.devices[0], names.CHUNK_PROGRAM,
                                         a, b)) == 2
    ctx = Ctx(tr, n_calls=3, chunk_size=1024)
    got = {m: _read(m, ctx) for m in METRICS}
    assert got == pytest.approx({
        "call_edge_ms": 5.439061666666667,
        "grid_argmin_ms": 0.02903366666666667,
        "chunk_gap_us": 9158.636666666665,
        "chunk_us_per_step": 100.36046891276042,
        "device_idle_frac": 0.4981566368647944}, rel=1e-9)
    assert 0 < tracing.busy_ns(tr.devices[0], lo, hi) < hi - lo
