"""The check's control fails: the plain reference computed one step below
the stated precision (bfloat16 device arithmetic, float32 sums) and put in
the program's place comes out not correct under each cell's limits."""

import json
import os

import pytest

import control
import run_cell
from harness import check

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = [w["name"] for w in json.load(open(os.path.join(
    os.path.dirname(BENCH), "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 1])
def test_control_fails_the_check(workload, seed):
    sizes = {"n_steps": 512}
    if workload.startswith("compose"):
        sizes["candidates"] = {"n_candidates": 60}
    r = control.readings(workload, seed, sizes)
    judged = check.judge(r, run_cell.resolve(workload)["limits"])
    assert any(not j["ok"] for j in judged.values()), r
