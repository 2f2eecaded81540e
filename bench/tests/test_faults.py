"""A run with the timed path broken underneath comes out not correct.

Each test skips the look for a chip, drives the rest of a run at a small
size on the CPU with one fault planted in the program, and checks
``correct``: a step that returns its state unchanged; half of the cells
left out (their results taken from the other half); an answer altered
where it is produced (one cell's power sum of one chunk 10 % high).  An
unbroken run passes.  The four-chip cell runs on four virtual CPU devices
in a process of its own, with one more fault: the exchange between chips
left out (the second chip's cells come back with the first chip's
results, as if its shard were never gathered).
"""

import contextlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import run_cell

SIZES = {"n_steps": 512, "chunk_size": 128}


def _sizes(workload):
    s = dict(SIZES)
    if workload.startswith("compose"):
        s["candidates"] = {"n_candidates": 40}
    return s


@contextlib.contextmanager
def _patched(attr, make):
    from repro.core import controller as ctl
    jit = ctl._fleet_stream_chunk_jit
    real = getattr(ctl, attr)
    setattr(ctl, attr, make(real))
    jit.clear_cache()          # the chunk program retraces with the fault
    try:
        yield
    finally:
        setattr(ctl, attr, real)
        jit.clear_cache()


def state_unchanged(real):
    def step(tables, cfg, carry, *args):
        _, out = real(tables, cfg, carry, *args)
        return carry, out
    return step


def _remap(real, src_of):
    """Run the stream, then give every cell the results of ``src_of(k)``."""
    import jax

    def stream(tables, traces, cfg, *a, **kw):
        fs = real(tables, traces, cfg, *a, **kw)
        lead = np.asarray(fs.mean_power_w).shape
        k = int(np.prod(lead))
        idx = src_of(k)

        def take(x):
            x = np.asarray(x)
            if x.shape[:len(lead)] != lead:
                return x
            flat = x.reshape((k,) + x.shape[len(lead):])
            return flat[idx].reshape(x.shape)
        return fs._replace(**{f: (jax.tree.map(take, v)
                                  if f != "n_steps" and f != "emitted"
                                  else v)
                              for f, v in fs._asdict().items()})
    return stream


def half_left_out(real):
    return _remap(real, lambda k: np.arange(k) % max(k // 2, 1))


def exchange_left_out(real):
    """The fleet axis is split over the devices in order; the second
    device's cells take the first device's results."""
    import jax

    def src_of(k):
        d = len(jax.devices())
        q = -(-k // d)
        idx = np.arange(k)
        idx[q:2 * q] = idx[:q][:len(idx[q:2 * q])]
        return idx
    return _remap(real, src_of)


def answer_altered(real):
    def chunk(*args, **kw):
        acc, ys = real(*args, **kw)
        return acc._replace(power_sum=acc.power_sum.at[0].multiply(1.1)), ys
    chunk.lower = real.lower          # the ahead-of-time warm-up
    return chunk


FAULTS = {
    "state_unchanged": ("_control_step", state_unchanged),
    "half_left_out": ("simulate_fleet_stream", half_left_out),
    "answer_altered": ("_fleet_stream_chunk_jit", answer_altered),
    "exchange_left_out": ("simulate_fleet_stream", exchange_left_out),
}
ONE_CHIP = ("state_unchanged", "half_left_out", "answer_altered")
CASES = ([("fpga5-paper.aggregate", f) for f in ONE_CHIP]
         + [("compose-fpga5.quickstart", f) for f in ONE_CHIP])
FOUR_CHIP = "compose-fpga5.quickstart-4chip"


def _run(workload):
    return run_cell.run(workload, 2 ** 31 + 77, 0.0, False,
                        require_chip=False, log=lambda m: None,
                        sizes=_sizes(workload))


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(workload, fault):
    attr, make = FAULTS[fault]
    with _patched(attr, make):
        res = _run(workload)
    assert res["correct"] is False, res["checks"]
    assert res["failed"] >= 1


@pytest.mark.parametrize("workload", ["fpga5-paper.aggregate",
                                      "compose-fpga5.quickstart"])
def test_unbroken_run_is_correct(workload):
    res = _run(workload)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def run_four(fault):
    """One run of the four-chip cell with ``fault`` planted (or none);
    prints the result.  Called in a process with four CPU devices."""
    import jax
    assert len(jax.devices()) == 4, jax.devices()
    if fault:
        with _patched(*FAULTS[fault]):
            res = _run(FOUR_CHIP)
    else:
        res = _run(FOUR_CHIP)
    print(json.dumps(res))


@pytest.mark.parametrize("fault", list(ONE_CHIP) + ["exchange_left_out",
                                                    None])
def test_four_chip_fault_is_not_correct(fault):
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.dirname(here)
    paths = [here, bench, os.path.join(os.path.dirname(bench), "src")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (f"import sys; sys.path[:0] = {paths!r}; "
            f"import test_faults; test_faults.run_four({fault!r})")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    if fault is None:
        assert res["correct"] is True, res["checks"]
    else:
        assert res["correct"] is False, res["checks"]
        assert res["failed"] >= 1
