"""Run one benchmark cell of the fleet power controller on the chip.

  python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout root:
the cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``); its limits for ``correct`` are
``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``.  A mix may state the ``techniques`` its cell
sweeps and ``controller`` settings over the configuration's; a
configuration may name its plain reference (``"reference": "<name>"``:
``bench/reference/<name>.py`` run over ``model.py``, whose functions it
replaces; default ``model`` alone).

A run: set-up (device check, compile cache, traffic from ``--seed``,
ahead-of-time compile of the cell's two programs, one warm-up call), then
the window (the entry point called again and again on the same inputs for
``--seconds``), then the check of every window call against the plain
reference (``bench/reference``).  ``--trace 1`` is a run of its own: the
profiler covers the configuration's ``trace_calls`` window calls (few
enough that the chip's trace buffer holds every event) and the per-layer
metrics are read from its trace.  The last line of standard output is one JSON result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax-cache")


class CellError(RuntimeError):
    """The run cannot produce a result (no chip, bad manifest)."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def configured(cfg: dict, mix: dict) -> dict:
    """The configuration as the mix runs it: the mix's ``techniques`` in
    place of the configuration's, its ``controller`` settings over the
    configuration's.  A mix that states neither leaves it as it is."""
    cfg = dict(cfg)
    if "techniques" in mix:
        cfg["techniques"] = list(mix["techniques"])
    if "controller" in mix:
        cfg["controller"] = {**cfg["controller"], **mix["controller"]}
    return cfg


def resolve(workload: str) -> dict:
    """The cell, its configuration (as its mix runs it), traffic, limits
    and metrics by name."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise CellError(f"no BENCHMARK.json at {ROOT}")
    man = load_json(path)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise CellError(f"unknown workload {workload!r}; known: "
                        f"{sorted(cells)}")
    cell = cells[workload]
    mix = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    return {
        "cell": cell,
        "config": configured(load_json(os.path.join(
            BENCH, "configs", cell["config"] + ".json")), mix),
        "mix": mix,
        "limits": load_json(os.path.join(BENCH, "limits",
                                         workload + ".json"))["limits"],
        "per_layer": man["per_layer"],
    }


def device_check(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise CellError(f"no TPU: JAX's device is {devices[0].platform!r}; "
                        "the benchmark does not fall back to it")
    if len(devices) < chips:
        raise CellError(f"the cell needs {chips} chips, JAX sees "
                        f"{len(devices)}")
    return devices


def metric_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(cfg: dict):
    """The plain reference the configuration names (``"reference":
    "<name>"``, default ``model``): ``bench/reference/model.py`` with
    ``bench/reference/<name>.py`` run over it in one namespace, so each
    function the named file defines replaces model's own wherever model's
    loop calls it."""
    name = cfg.get("reference", "model")
    mod = types.ModuleType("bench_reference_" + name)
    mod.__file__ = os.path.join(BENCH, "reference", name + ".py")
    sys.modules[mod.__name__] = mod  # dataclasses look their module up
    for part in dict.fromkeys(("model", name)):
        path = os.path.join(BENCH, "reference", part + ".py")
        with open(path) as f:
            exec(compile(f.read(), path, "exec"), mod.__dict__)
    return mod


class CompileWatch:
    """Counts XLA compilations while armed (JAX's monitoring events)."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.events = (dispatch.BACKEND_COMPILE_EVENT,)
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.events:
            self.count += 1


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, log=None, sizes: dict = None) -> dict:
    """One run of a cell; returns the result object (the last line).

    ``require_chip=False`` skips the look for a chip and ``sizes``
    overrides configuration sizes: both only for the CPU tests."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    # The persistent compile cache lives at a fixed path in the checkout,
    # whatever the environment names.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    spec = resolve(workload)
    cell, cfg, mix = spec["cell"], spec["config"], spec["mix"]
    for k, v in (sizes or {}).items():
        if isinstance(v, dict):
            cfg[k] = {**cfg[k], **v}
        else:
            cfg[k] = v
    import jax
    devices = device_check(cell["chips"]) if require_chip else jax.devices()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import check, names, program, tracing
    from harness import traffic as traffic_gen
    from repro.core import aot
    from repro.core import controller as ctl

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    cache = aot.enable_compilation_cache()
    log(f"# {workload}: seed {seed}, device {devices[0].device_kind} "
        f"x{len(devices)}, compile cache {cache}")
    scen = traffic_gen.generate(mix, cfg["n_steps"], seed)
    candidates = None
    if cfg["entry"] == "composition":
        cc = cfg["candidates"]
        candidates = traffic_gen.enumerate_candidates(
            len(cfg["platforms"]), cc["max_nodes"], cc["n_candidates"], seed)
    entry = program.entry(cfg, mix, scen, candidates)
    t0 = time.perf_counter()
    aot_s = entry.warm()
    entry.call()                                  # warm-up: same shapes
    setup_s = time.perf_counter() - T_START
    log(f"# set-up {setup_s:.3f} s (traffic+AOT {t0 - T_START:.3f} s, "
        f"AOT {aot_s}, warm-up call {time.perf_counter() - t0:.3f} s)")

    watch = CompileWatch()
    traced_before = ctl.fleet_trace_counts()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    outs, walls, raised = [], [], None
    watch.armed = True
    w0 = time.perf_counter()
    try:
        while True:
            c0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(names.CALL_SPAN):
                out = entry.call()
            walls.append(time.perf_counter() - c0)
            outs.append((out, entry.tables[0]))
            if trace and len(walls) >= cfg["trace_calls"]:
                break
            if time.perf_counter() - w0 >= seconds:
                break
    except Exception as e:  # noqa: BLE001 — a failed call fails the run
        raised = f"{type(e).__name__}: {e}"
        log(f"# window call raised {raised}")
    finally:
        watch.armed = False
        if trace:
            jax.profiler.stop_trace()
    window_wall = time.perf_counter() - w0
    compiles = watch.count
    retraced = ctl.fleet_trace_counts() != traced_before
    entry.restore()
    outs = [(o, check.host_tables(t)) for o, t in outs]
    log(f"# window: {len(walls)} calls in {window_wall:.3f} s, call walls "
        f"{[round(w, 4) for w in walls]}, compiles {compiles}, "
        f"retraced {retraced}")

    mem = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    metrics, device_extra, breakdown = {}, {}, None
    steps = entry.n_cells * entry.n_steps
    if trace and walls:
        path = tracing.find(log_dir)
        tr = tracing.load(path)
        ctx = Context(tr, len(walls), entry.chunk_size)
        for m in spec["per_layer"]:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lo, hi = tr.window()
        busy = [tracing.busy_ns(d, lo, hi) for d in tr.devices]
        device_extra = {"busy_s": (sum(busy) / max(len(busy), 1)) / 1e9,
                        "window_s": (hi - lo) / 1e9}
        breakdown = {"device_ops": tracing.device_ops(tr),
                     "idle_gaps": tracing.idle_gaps(tr)}
        shutil.rmtree(log_dir, ignore_errors=True)
    elif walls:
        metrics["cell_steps_per_s"] = {
            "value": steps * len(walls) / sum(walls), "unit": "cell-steps/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    # Check: free the program's device state, then the reference.
    del entry
    r0 = time.perf_counter()
    per_call = []
    if outs:
        ref_model = reference(cfg)
        names_ = [program.PREFIX + sc.name for sc in scen]
        if cfg["entry"] == "campaign":
            ref = ref_model.campaign(cfg, mix, scen)
            per_call = [check.gaps("campaign", check.campaign_stats(
                cfg, o, names_, mix.get("tenants") is not None), t, ref)
                for o, t in outs]
        else:
            ref = ref_model.composition(cfg, scen, candidates)
            per_call = [check.gaps("composition", {
                "total_power_w": o.total_power_w,
                "qos_violation_rate": o.qos_violation_rate,
                "served_fraction": o.served_fraction}, t, ref)
                for o, t in outs]
    log(f"# reference and check {time.perf_counter() - r0:.3f} s")

    judged = (check.judge(check.worst(per_call), spec["limits"])
              if per_call else {})
    judged["window_compiles"] = {"value": compiles, "limit": 0,
                                 "ok": compiles == 0 and not retraced}
    failed = sum(not all(j["ok"] for j in
                         check.judge(g, spec["limits"]).values())
                 for g in per_call) + (1 if raised else 0)
    correct = bool(walls) and raised is None and all(
        j["ok"] for j in judged.values())
    for k, j in judged.items():
        log(f"check {k}: {j['value']:.6g} limit {j['limit']} "
            f"{'ok' if j['ok'] else 'FAIL'}")
    d0 = devices[0]
    result = {
        "correct": correct,
        "attempted": len(walls) + (1 if raised else 0),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": cell["chips"], "memory_peak_bytes": peak,
                   **device_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: [j["value"], j["limit"]] for k, j in judged.items()}
    return result


class Context:
    """What a per-layer metric reader may read: the loaded trace, the
    number of entry calls it covers and the cell's chunk size."""

    def __init__(self, trace, n_calls: int, chunk_size: int):
        self.trace = trace
        self.n_calls = n_calls
        self.chunk_size = chunk_size


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except CellError as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
