"""A cell is added as data: a configuration, a traffic mix and a metric
dropped in as new files (plus their BENCHMARK.json entries) are found by
name, with no edit to any file the benchmark already has."""

import importlib.util
import json
import os
import shutil
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = _copy(tmp_path)
    b = root / "bench"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}

    cfg = json.loads((b / "configs" / "fpga5-paper.json").read_text())
    cfg.update(name="fpga2-small", platforms=["tabla", "stripes"],
               techniques=["proposed", "freq_only"], n_steps=256,
               chunk_size=128)
    (b / "configs" / "fpga2-small.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "aggregate.json").read_text())
    mix["scenarios"] = {k: mix["scenarios"][k]
                        for k in ("burse", "ramp", "node_failure")}
    (b / "traffic" / "three.json").write_text(json.dumps(mix))
    (b / "limits" / "fpga2-small.three.json").write_text(json.dumps(
        {"limits": {"power": 1e-3}}))
    (b / "metrics" / "calls_traced.py").write_text(
        "def read(ctx):\n    return float(ctx.n_calls)\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "fpga2-small", "source": "test",
                           "file": "bench/configs/fpga2-small.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "fpga2-small.three",
                             "config": "fpga2-small", "traffic": "three",
                             "chips": 1, "why": "test"})
    man["per_layer"].append({"name": "calls_traced", "unit": "calls",
                             "better": "higher", "source": "device_trace",
                             "layer": "device", "moves": "cell_steps_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    run_cell = _module(b / "run_cell.py", "run_cell_copy")
    spec = run_cell.resolve("fpga2-small.three")
    assert spec["config"]["platforms"] == ["tabla", "stripes"]
    assert sorted(spec["mix"]["scenarios"]) == ["burse", "node_failure",
                                                "ramp"]
    assert spec["limits"] == {"power": 1e-3}
    names = [m["name"] for m in spec["per_layer"]]
    assert "calls_traced" in names and "grid_argmin_ms" in names

    class Ctx:
        n_calls = 3
    assert run_cell.metric_reader("calls_traced")(Ctx()) == 3.0

    traffic = _module(b / "harness" / "traffic.py", "traffic_copy")
    got = traffic.generate(spec["mix"], 256, 5)
    assert [g.name for g in got] == ["burse", "ramp", "node_failure"]
    assert got[2].nodes is not None and got[0].nodes is None
    assert np.all((got[1].trace > -1) & (got[1].trace < 2))

    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
