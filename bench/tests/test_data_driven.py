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


def test_new_reference_module_and_mix_techniques_are_found_by_name(tmp_path):
    """A configuration names its own plain reference (a new module that
    defines only the functions its mechanism changes, run over
    ``model.py``) and a mix states the techniques and controller settings
    its cell sweeps: both are found by name, model's loop calls the new
    module's function, and the harness, the control and the bench tests
    all see the same configuration."""
    root = _copy(tmp_path)
    b = root / "bench"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}

    (b / "reference" / "tiered.py").write_text(
        "def headroom_bump(tab, reserve, a_hat, selected, b_agg, n, m, p):\n"
        "    return selected\n")
    cfg = json.loads((b / "configs" / "fpga5-paper.json").read_text())
    cfg.update(name="fpga5-tiered", reference="tiered", n_steps=512,
               chunk_size=128)
    (b / "configs" / "fpga5-tiered.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "aggregate.json").read_text())
    mix["scenarios"] = {k: mix["scenarios"][k] for k in ("burse",
                                                         "node_failure")}
    mix["techniques"] = ["hybrid", "headroom"]
    mix["controller"] = {"headroom_frac": 0.25, "avail_predictor": {
        "kind": "persistence", "warmup_steps": 32}}
    (b / "traffic" / "headroom-two.json").write_text(json.dumps(mix))
    (b / "limits" / "fpga5-tiered.headroom-two.json").write_text(
        json.dumps({"limits": {"power": 1e-3}}))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "fpga5-tiered", "source": "test",
                           "file": "bench/configs/fpga5-tiered.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "fpga5-tiered.headroom-two",
                             "config": "fpga5-tiered",
                             "traffic": "headroom-two", "chips": 1,
                             "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    run_cell = _module(b / "run_cell.py", "run_cell_copy2")
    spec = run_cell.resolve("fpga5-tiered.headroom-two")
    cfg = spec["config"]
    assert cfg["techniques"] == ["hybrid", "headroom"]
    assert cfg["controller"]["headroom_frac"] == 0.25
    assert cfg["controller"]["n_nodes"] == 8            # the rest kept
    ref = run_cell.reference(cfg)
    assert ref.__file__ == str(b / "reference" / "tiered.py")
    plain_ref = run_cell.reference({**cfg, "reference": "model"})
    assert plain_ref.__file__ == str(b / "reference" / "model.py")

    traffic = _module(b / "harness" / "traffic.py", "traffic_copy2")
    scen = traffic.generate(spec["mix"], cfg["n_steps"], 5)
    out = ref.campaign(cfg, spec["mix"], scen)
    base = plain_ref.campaign(cfg, spec["mix"], scen)
    assert out["mean_power_w"].shape == (5 * 2 * 2,)
    tabs = ref.operating_tables(cfg)
    assert tabs["reserve"].tolist() == [[0.0, 0.25]] * 5
    assert np.array_equal(tabs["power"][:, 0], tabs["power"][:, 1])
    # [P, T, S]: with the bump replaced, headroom runs as hybrid does.
    power = out["mean_power_w"].reshape(5, 2, 2)
    assert np.array_equal(power[:, 1], power[:, 0])
    assert np.array_equal(out["mean_power_w"].reshape(5, 2, 2)[:, 0],
                          base["mean_power_w"].reshape(5, 2, 2)[:, 0])
    assert not np.array_equal(base["mean_power_w"].reshape(5, 2, 2)[:, 1],
                              power[:, 1])

    program = _module(b / "harness" / "program.py", "program_copy2")
    kw = program.controller_kwargs(cfg, spec["mix"])
    assert kw["headroom_frac"] == 0.25
    assert kw["avail_predictor"].warmup_steps == 32
    plain = run_cell.resolve("fpga5-paper.aggregate")["config"]
    assert "headroom_frac" not in program.controller_kwargs(plain, {})
    assert run_cell.reference(plain).__file__ == str(b / "reference"
                                                     / "model.py")

    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_tiered_mix_of_inline_components_is_found_and_runs(tmp_path,
                                                           compile_cache):
    """A tiered deployment is data: a mix whose tenants are inline
    components, each with its tier's class, run by the priority
    scheduler, with its limits and a ``BENCHMARK.json`` entry, is found
    by name and runs at a test's size with no other edit.  While the
    program's scheduler lands one bin low, the run is made again with the
    level computed as stated (``test_reference.retry_with_stated_level``)."""
    from test_reference import retry_with_stated_level
    root = _copy(tmp_path)
    b = root / "bench"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}

    tiers = [  # ClusterData 2019 bands: production, mid, best-effort batch
        ({"shape": "periodic", "params": {"period_max": 96,
                                          "mean_load": 0.3, "burst": 0.1}},
         200.0, 0.0),
        ({"shape": "bursty", "params": {"mean_load": 0.4, "hurst": 0.8}},
         117.0, 2.0),
        ({"shape": "ramp", "params": {"lo": 0.1, "hi": 0.5, "noise": 0.05}},
         112.0, 8.0)]
    mix = {"about": "test", "tenants": "auto", "scheduler": "priority",
           "scenarios": {"tiers": {
               "shape": "mix", "weights": [0.5, 0.3, 0.2],
               "components": [dict(c, **{"class": {
                   "priority": p, "latency_target": lt}})
                   for c, p, lt in tiers]}}}
    (b / "traffic" / "tiers3.json").write_text(json.dumps(mix))
    (b / "limits" / "fpga5-paper.tiers3.json").write_text(json.dumps(
        {"limits": {"tables_power": 1e-3, "tables_volt": 0.0125,
                    "power": 1e-3, "qos": 0.02, "served": 0.01,
                    "backlog": 0.05, "mispred": 0.005, "tenant_qos": 0.02,
                    "tenant_served": 0.01}}))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": "fpga5-paper.tiers3",
                             "config": "fpga5-paper", "traffic": "tiers3",
                             "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    run_cell = _module(b / "run_cell.py", "run_cell_copy3")
    run_cell.CACHE_DIR = compile_cache
    spec = run_cell.resolve("fpga5-paper.tiers3")
    traffic = _module(b / "harness" / "traffic.py", "traffic_copy3")
    (scen,) = traffic.generate(spec["mix"], 512, 5)
    assert scen.tenants[0].shape == (3, 512)
    assert scen.tenants[1]["priority"] == [200.0, 117.0, 112.0]
    assert scen.tenants[1]["latency_target"] == [0.0, 2.0, 8.0]

    res = retry_with_stated_level(
        lambda: run_cell.run("fpga5-paper.tiers3", 2 ** 31 + 5, 0.0, False,
                             require_chip=False, log=lambda m: None,
                             sizes={"n_steps": 512, "chunk_size": 128}),
        lambda r: not r["correct"])
    assert res["correct"] is True, res["checks"]
    assert {"tenant_qos", "tenant_served"} <= set(res["checks"])

    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
