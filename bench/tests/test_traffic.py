"""The traffic copies reproduce the program's generators bit for bit."""

import json
import os

import numpy as np
import pytest

from harness import traffic as tg

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIELDS = ("priority", "latency_target", "share")
MIXES = ("aggregate", "tenants-priority", "quickstart")


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 12345])
def test_copies_match_program_generators(mix, seed):
    from repro.core import scenarios as scn
    from repro.core.scheduler import make_tenants
    n = 700
    for got in tg.generate(_mix(mix), n, seed):
        s = scn.get_scenario(got.name)
        raw = np.asarray(s.build(n, s._rng(seed)))
        assert raw.dtype == got.trace.dtype and np.array_equal(raw, got.trace)
        if s.nodes is None:
            assert got.nodes is None
        else:
            assert np.array_equal(
                np.asarray(s.nodes(n, s._rng(seed, "/nodes"))), got.nodes)
        if got.tenants is None:
            continue
        if s.tenants is not None:
            parts, spec = s.tenants(n, s._rng(seed))
            mine = make_tenants(*[got.tenants[1][k] for k in FIELDS])
            for f in FIELDS:
                assert np.array_equal(np.asarray(getattr(spec, f)),
                                      np.asarray(getattr(mine, f))), f
        else:
            parts = s.build.components(n, s._rng(seed))
        assert np.array_equal(np.asarray(parts, np.float64), got.tenants[0])


def test_candidates_match_program():
    from repro.core import composition as comp
    for seed in (0, 2 ** 31 + 7):
        assert np.array_equal(tg.enumerate_candidates(5, 8, 1000, seed),
                              comp.enumerate_candidates(5, 8, 1000, seed))


def test_same_seed_same_traffic_and_sizes_fixed():
    mix = _mix("aggregate")
    a = tg.generate(mix, 300, 9)
    b = tg.generate(mix, 300, 9)
    c = tg.generate(mix, 300, 10)
    for x, y, z in zip(a, b, c):
        assert np.array_equal(x.trace, y.trace)
        assert x.trace.shape == z.trace.shape
