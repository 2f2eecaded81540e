"""Benchmark harness — one entry per paper table/figure + TPU adaptation.

Run:  PYTHONPATH=src python -m benchmarks.run [--steps N] [--only SUBSTRS]
Prints ``name,us_per_call,derived`` CSV rows (derived = the table's
headline metric; derived-only rows leave ``us_per_call`` empty in the
CSV and ``null`` in the JSON) and writes the same rows to
``BENCH_fleet.json`` so the perf trajectory is trackable across PRs.
``--only table2,fleet`` with ``--steps 64`` is the CI smoke subset.
The persistent JAX compilation cache (``repro.core.aot``) is on, so
repeat runs skip XLA compilation of the fleet programs.  A bench that
raises prints an ``ERROR:`` row and the run exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax.numpy as jnp
import numpy as np

from repro.core import aot
from repro.core import controller as ctl
from repro.core import predictors as pred_mod
from repro.core import voltage as volt
from repro.core import workload as wl
from repro.core.accelerators import ACCELERATORS, PAPER_TABLE_II

#: Default control-trace length; overridden by ``--steps`` for smoke runs.
N_STEPS = 1024


def _timeit(fn, n=5):
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def _trace(n=None, seed=0):
    return wl.generate_trace(
        wl.WorkloadConfig(n_steps=n or N_STEPS, seed=seed))


def bench_table2():
    """Paper Table II: power reduction per accelerator × technique."""
    trace = _trace()
    rows = []
    gains = {}
    for name, acc in ACCELERATORS.items():
        plat = ctl.fpga_platform(acc)
        t0 = time.perf_counter()
        res = ctl.compare_all(plat, trace)
        dt = (time.perf_counter() - t0) / len(res) / len(trace) * 1e6
        for tech, s in res.items():
            gains.setdefault(tech, []).append(s.power_gain)
            paper = PAPER_TABLE_II.get(tech, {}).get(name)
            derived = (f"gain={s.power_gain:.2f}x"
                       + (f";paper={paper:.1f}x" if paper else ""))
            rows.append((f"table2/{name}/{tech}", dt, derived))
    for tech in ("proposed", "core_only", "bram_only"):
        avg = float(np.mean(gains[tech]))
        rows.append((f"table2/average/{tech}", None,
                     f"gain={avg:.2f}x;paper="
                     f"{PAPER_TABLE_II[tech]['average']}x"))
    return rows


def bench_fig4_workload_sweep():
    """Fig. 4: technique efficiency vs workload level (α=0.2, β=0.4)."""
    plat = ctl.analytic_platform(alpha=0.2, beta=0.4)
    rows = []
    for load in (0.1, 0.3, 0.5, 0.7, 0.9):
        trace = np.full(256, load)
        for tech in ("proposed", "core_only", "bram_only", "power_gating"):
            s = ctl.run_technique(plat, trace, tech, n_nodes=64)
            rows.append((f"fig4/load{load:.1f}/{tech}", None,
                         f"gain={s.power_gain:.2f}x"))
    return rows


def bench_fig5_alpha_sweep():
    """Fig. 5: sensitivity to the critical path's BRAM share α (50 % load)."""
    rows = []
    trace = np.full(256, 0.5)
    for alpha in (0.0, 0.1, 0.2, 0.4, 0.8):
        plat = ctl.analytic_platform(alpha=alpha, beta=0.4)
        for tech in ("proposed", "core_only", "bram_only"):
            s = ctl.run_technique(plat, trace, tech)
            rows.append((f"fig5/alpha{alpha:.1f}/{tech}", None,
                         f"gain={s.power_gain:.2f}x"))
    return rows


def bench_fig6_beta_sweep():
    """Fig. 6: sensitivity to the BRAM power share β (50 % load)."""
    rows = []
    trace = np.full(256, 0.5)
    for beta in (0.1, 0.25, 0.5, 1.0, 2.0):
        plat = ctl.analytic_platform(alpha=0.2, beta=beta)
        for tech in ("proposed", "core_only", "bram_only"):
            s = ctl.run_technique(plat, trace, tech)
            rows.append((f"fig6/beta{beta:.2f}/{tech}", None,
                         f"gain={s.power_gain:.2f}x"))
    return rows


def bench_fig10_trace():
    """Fig. 10/11: Tabla under the bursty trace — power + voltages."""
    plat = ctl.fpga_platform(ACCELERATORS["tabla"])
    trace = _trace()
    cfg = ctl.ControllerConfig(technique="proposed")
    t0 = time.perf_counter()
    res = ctl.simulate(plat, cfg, trace)
    us = (time.perf_counter() - t0) / len(trace) * 1e6
    s = ctl.summarize(plat, cfg, trace, res)
    vc = np.asarray(res.v_core)
    vb = np.asarray(res.v_bram)
    derived = (f"gain={s.power_gain:.2f}x"
               f";vcore=[{vc.min():.2f},{vc.max():.2f}]"
               f";vbram=[{vb.min():.2f},{vb.max():.2f}]"
               f";mispred={s.misprediction_rate:.3f}"
               f";qos_viol={s.qos_violation_rate:.3f}")
    return [("fig10/tabla/proposed_trace", us, derived)]


def bench_fig12_per_accelerator_traces():
    """Fig. 12: proposed-technique efficiency across all five accelerators."""
    trace = _trace()
    rows = []
    for name, acc in ACCELERATORS.items():
        plat = ctl.fpga_platform(acc)
        res = ctl.simulate(plat, ctl.ControllerConfig(), trace)
        s = ctl.summarize(plat, ctl.ControllerConfig(), trace, res)
        vb = np.asarray(res.v_bram)
        rows.append((f"fig12/{name}", None,
                     f"gain={s.power_gain:.2f}x;min_vbram={vb.min():.2f}"))
    return rows


def bench_predictor():
    """Predictor-registry sweep: gain-vs-misprediction, fleet-wide.

    Every registered forecaster (markov/persistence/ewma/holt_winters/
    hierarchy/seasonal_naive) runs the *whole* scenario + replay library
    through the streaming campaign path, one campaign per family
    (per-family compile is the contract; same-family sweeps reuse the
    programs).  Per (kind, scenario) row: ``exact`` and ``margin``
    accuracy (exact-bin charges misses the controller's t% margin
    absorbs by design; margin-aware is the honest "flying blind" axis),
    power ``gain``, and ``qos`` violation rate — the sensitivity record
    for how much prediction quality buys in power without costing QoS.

    Campaigns run ``2·N_STEPS`` so a replayed trace spans more than one
    full period — the regime where period-aware forecasters are even
    learnable.  ``seasonal_naive`` goes through its measure-then-
    configure workflow (``seasonal.config_for_trace``): scenarios are
    grouped by detected exact tiling period and each group runs as its
    own fitted campaign (``season`` is static config — one compile per
    distinct period, zero retraces within a group).  The per-kind
    ``predictor/<kind>/trace`` row times one ``evaluate_trace`` scan on
    the canonical bursty trace (the seed's host loop paid 2 dispatches
    per step).
    """
    from repro.core import scenarios as scn
    from repro.core.predictors import seasonal
    trace = _trace(2 * N_STEPS)
    platforms = [ctl.fpga_platform(ACCELERATORS["tabla"])]
    names = tuple(sorted(scn.SCENARIOS))
    n_steps = 2 * N_STEPS
    chunk = max(min(N_STEPS, 512), 1)
    rows = []

    def campaign_rows(kind, group_names, predictor):
        camp = scn.run_campaign(platforms, scenario_names=group_names,
                                techniques=("proposed",), n_steps=n_steps,
                                chunk_size=chunk, predictor=predictor)
        for scen in camp["scenarios"]:
            cell = camp["table"][platforms[0].name]["proposed"][scen]
            rows.append((
                f"predictor/{kind}/{scen}", None,
                f"exact={1.0 - cell['misprediction_rate']:.3f}"
                f";margin={1.0 - cell['margin_misprediction_rate']:.3f}"
                f";gain={cell['power_gain']:.2f}x"
                f";qos={cell['qos_violation_rate']:.3f}"))

    for kind in pred_mod.available():
        cfg = pred_mod.PredictorConfig(kind=kind, n_bins=25,
                                       warmup_steps=32, margin_bins=1)
        out = pred_mod.evaluate_trace(cfg, trace)   # warm/compile
        out.predicted.block_until_ready()
        t0 = time.perf_counter()
        out = pred_mod.evaluate_trace(cfg, trace)
        out.predicted.block_until_ready()
        us = (time.perf_counter() - t0) / len(trace) * 1e6
        rows.append((f"predictor/{kind}/trace", us,
                     f"exact={float(out.exact_accuracy):.3f}"
                     f";margin={float(out.margin_accuracy):.3f}"))
        if kind == "seasonal_naive":
            by_season = {}
            for scen in names:
                w = scn.get_scenario(scen).trace(n_steps, seed=0)
                fitted = seasonal.config_for_trace(cfg, w)
                by_season.setdefault(fitted.season, []).append(scen)
            for season, group in sorted(by_season.items()):
                campaign_rows(kind, tuple(group),
                              dataclasses.replace(cfg, season=season))
        else:
            campaign_rows(kind, names, cfg)
    return rows


def bench_fleet():
    """The fused fleet engine vs the seed's per-cell loop (Table II sweep).

    Same 5 accelerators × 5 techniques × bursty trace; the per-cell path
    re-closes and retraces every cell, the batched path compiles two
    programs and vmaps the rest.
    """
    trace = _trace()
    platforms = [ctl.fpga_platform(acc) for acc in ACCELERATORS.values()]
    # One-time backend init shouldn't be charged to either path.
    jnp.zeros(1).block_until_ready()

    t0 = time.perf_counter()
    percell = {p.name: ctl.compare_all(p, trace) for p in platforms}
    t_cell = time.perf_counter() - t0

    t0 = time.perf_counter()
    fleet = ctl.compare_all_batched(platforms, trace)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    fleet = ctl.compare_all_batched(platforms, trace)
    t_warm = time.perf_counter() - t0

    err = max(abs(fleet[n][t].power_gain - percell[n][t].power_gain)
              for n in fleet for t in fleet[n])
    cells = sum(len(v) for v in fleet.values())
    counts = ctl.fleet_trace_counts()
    return [
        ("fleet/percell_loop", t_cell / cells * 1e6, "seed_path"),
        ("fleet/batched_cold", t_cold / cells * 1e6,
         f"speedup={t_cell / t_cold:.1f}x;max_gain_err={err:.1e}"),
        ("fleet/batched_warm", t_warm / cells * 1e6,
         f"speedup={t_cell / t_warm:.1f}x"
         f";traces=tables:{counts['tables']}/simulate:{counts['simulate']}"),
    ]


def bench_hybrid():
    """Hybrid node-scaling + DVFS vs proposed / power-gating (fleet path).

    The node-count gears ride the same masked grid sweep as the DVFS
    techniques, so the whole comparison is still two compiled programs.
    ``mean_nodes`` is the average powered-on node count under the bursty
    trace; the closed-loop row drives the serving batcher with the
    controller's f_rel in the loop and reports measured latency.
    """
    trace = _trace()
    platforms = [ctl.fpga_platform(acc) for acc in ACCELERATORS.values()]
    techniques = ("proposed", "power_gating", "hybrid")
    t0 = time.perf_counter()
    fleet = ctl.compare_all_batched(platforms, trace, techniques=techniques)
    dt = (time.perf_counter() - t0) / (len(platforms) * len(techniques)) \
        / len(trace) * 1e6
    rows = []
    for name, plat in zip(ACCELERATORS, platforms):
        res = fleet[plat.name]
        sim = ctl.simulate(plat, ctl.ControllerConfig(technique="hybrid"),
                           trace)
        rows.append((f"hybrid/{name}", dt,
                     f"hybrid={res['hybrid'].power_gain:.2f}x"
                     f";prop={res['proposed'].power_gain:.2f}x"
                     f";pg={res['power_gating'].power_gain:.2f}x"
                     f";mean_nodes={float(np.mean(np.asarray(sim.n_active))):.2f}"))

    from repro.serving.autoscale import DvfsServingSimulator, RooflineTerms
    terms = RooflineTerms(t_compute=0.002, t_memory=0.012, t_collective=0.001)
    # Short predictor warmup so even the 64-step CI smoke leaves the
    # pinned-top-bin phase and actually exercises the closed loop.
    sim = DvfsServingSimulator(
        terms=terms, steps_per_tau=16,
        controller_cfg=ctl.ControllerConfig(
            technique="hybrid", n_nodes=8,
            predictor=pred_mod.PredictorConfig(warmup_steps=4)))
    lam = np.full(max(4 * N_STEPS, 256), 1.0)
    t0 = time.perf_counter()
    out = sim.run_request_load(lam, batch_size=32, mean_new_tokens=8)
    us = (time.perf_counter() - t0) / len(lam) * 1e6
    s = out["summary"]
    rows.append(("hybrid/closed_loop_serving", us,
                 f"gain={s.power_gain:.2f}x;occ={out['occupancy_tau'].mean():.2f}"
                 f";p50={s.latency_p50:.0f};p99={s.latency_p99:.0f}"
                 f";completed={out['completed']}"))
    return rows


def bench_campaign():
    """Scenario-library campaign through the streaming fleet path.

    Platforms × techniques × scenarios in one chunked streaming program;
    per-scenario power-gain/QoS cells land in the bench JSON.  The
    ``stream`` trace count is reported so retraces across same-shaped
    scenario sweeps show up in the perf record.
    """
    from repro.core import scenarios as scn
    platforms = [ctl.fpga_platform(ACCELERATORS[n])
                 for n in ("tabla", "stripes")]
    names = ("burse", "diurnal", "flash_crowd", "node_failure")
    techniques = ("proposed", "power_gating", "hybrid")
    chunk = max(min(N_STEPS, 512), 1)
    t0 = time.perf_counter()
    out = scn.run_campaign(platforms, scenario_names=names,
                           techniques=techniques, n_steps=N_STEPS,
                           chunk_size=chunk)
    dt = time.perf_counter() - t0
    cells = len(platforms) * len(techniques) * len(names)
    rows = []
    for scen in names:
        per_tech = {}
        for tech in techniques:
            per_tech[tech] = np.mean([out["table"][p.name][tech][scen]
                                      ["power_gain"] for p in platforms])
        qos = np.mean([out["table"][p.name]["proposed"][scen]
                       ["qos_violation_rate"] for p in platforms])
        rows.append((f"campaign/{scen}", dt / cells / N_STEPS * 1e6,
                     f"prop={per_tech['proposed']:.2f}x"
                     f";pg={per_tech['power_gating']:.2f}x"
                     f";hyb={per_tech['hybrid']:.2f}x"
                     f";qos_viol={qos:.3f}"))
    # Second same-shaped campaign (new seed) must reuse the compiled
    # chunk program — the stream count delta is the retrace regression.
    before = ctl.fleet_trace_counts()["stream"]
    scn.run_campaign(platforms, scenario_names=names, techniques=techniques,
                     n_steps=N_STEPS, chunk_size=chunk, seed=1)
    delta = ctl.fleet_trace_counts()["stream"] - before
    rows.append(("campaign/stream_reuse", None,
                 f"retraces={delta};chunk={chunk}"))
    return rows


def bench_failure():
    """Faithful node-failure campaign through the streaming path.

    The node_failure scenario's per-step usable-nodes schedule rides the
    same [K, C] chunks as the workload: the controller clamps
    provisioning to the survivors, dead nodes draw 0 W, and the headline
    ``gain`` is priced against the *available* fleet
    (``vs_cfg`` keeps the configured-fleet comparison).  After a healthy
    same-shaped warm-up sweep the availability-bearing sweep must add no
    compiled chunk programs (``failure/stream_reuse`` should report 0).
    """
    from repro.core import scenarios as scn
    platforms = [ctl.fpga_platform(ACCELERATORS[n])
                 for n in ("tabla", "stripes")]
    techniques = ("proposed", "power_gating", "hybrid", "headroom")
    fail_scens = ("node_failure", "rack_failure", "cascade", "flaky_fleet")
    chunk = max(min(N_STEPS, 512), 1)
    kw = dict(techniques=techniques, n_steps=N_STEPS, chunk_size=chunk)
    # Healthy warm-up sweep of the same fleet shape (same scenario
    # count), so the failure-bearing sweep below must be a pure reuse.
    scn.run_campaign(platforms, scenario_names=(
        "burse", "diurnal", "flash_crowd", "ramp", "decay"), **kw)
    before = ctl.fleet_trace_counts()["stream"]
    t0 = time.perf_counter()
    out = scn.run_campaign(platforms,
                           scenario_names=("burse",) + fail_scens, **kw)
    dt = time.perf_counter() - t0
    delta = ctl.fleet_trace_counts()["stream"] - before
    cells = len(platforms) * len(techniques) * (1 + len(fail_scens))
    rows = []

    def mean_cell(tech, scen):
        cell = [out["table"][p.name][tech][scen] for p in platforms]
        return {k: float(np.mean([c[k] for c in cell]))
                for k in ("power_gain", "power_gain_vs_configured",
                          "mean_avail_nodes", "qos_violation_rate")}

    for tech in techniques:
        c = mean_cell(tech, "node_failure")
        rows.append((f"failure/node_failure/{tech}",
                     dt / cells / N_STEPS * 1e6,
                     f"gain={c['power_gain']:.2f}x"
                     f";vs_cfg={c['power_gain_vs_configured']:.2f}x"
                     f";avail={c['mean_avail_nodes']:.2f}"
                     f";qos_viol={c['qos_violation_rate']:.3f}"))
    # Correlated failure models: the headroom-vs-hybrid trade per shape.
    for scen in fail_scens[1:]:
        h, y = mean_cell("hybrid", scen), mean_cell("headroom", scen)
        rows.append((f"failure/{scen}", None,
                     f"hyb={h['power_gain']:.2f}x"
                     f"/q{h['qos_violation_rate']:.3f}"
                     f";hr={y['power_gain']:.2f}x"
                     f"/q{y['qos_violation_rate']:.3f}"
                     f";avail={y['mean_avail_nodes']:.2f}"))
    # Pareto front over (power_gain ↑, qos_violation ↓) per failure
    # scenario (platform-mean cells — the campaign also reports
    # per-platform fronts in run_campaign()["pareto"]).
    for scen in fail_scens:
        front = scn.pareto_front({t: mean_cell(t, scen)
                                  for t in techniques})
        rows.append((f"failure/pareto/{scen}", None,
                     "front=" + ",".join(front)))
    # The ISSUE-9 acceptance gate: headroom must hold QoS violation
    # under 0.5 on node_failure while keeping gain >= 2.5x.
    g = mean_cell("headroom", "node_failure")
    gate_ok = g["qos_violation_rate"] < 0.5 and g["power_gain"] >= 2.5
    rows.append(("failure/headroom_gate", None,
                 f"qos_viol={g['qos_violation_rate']:.3f}"
                 f";gain={g['power_gain']:.2f}x;ok={int(gate_ok)}"))
    rows.append(("failure/stream_reuse", None,
                 f"retraces={delta};chunk={chunk}"))
    return rows


def bench_replay():
    """Bundled-trace replay through the streaming campaign path.

    Replays the vendored Azure/Google-style samples (and the composed
    ``cloud_mix``) as campaign scenarios and asserts the zero-retrace
    contract end-to-end: after a same-shaped *synthetic* warm-up sweep,
    the replay sweep must add no compiled chunk programs
    (``replay/stream_reuse`` reports the retrace delta — it should be 0).
    """
    from repro.core import scenarios as scn
    from repro.core import traces as tr
    replays = ("replay_azure_vm_cpu", "replay_google_cluster", "cloud_mix")
    missing = [n for n in replays if n not in scn.SCENARIOS]
    if missing:
        return [("replay/skipped", None, f"no bundled traces: {missing}")]
    platforms = [ctl.fpga_platform(ACCELERATORS["tabla"])]
    techniques = ("proposed", "power_gating", "hybrid")
    chunk = max(min(N_STEPS, 512), 1)
    kw = dict(techniques=techniques, n_steps=N_STEPS, chunk_size=chunk)
    scn.run_campaign(platforms, scenario_names=("burse", "diurnal", "ramp"),
                     **kw)
    before = ctl.fleet_trace_counts()["stream"]
    t0 = time.perf_counter()
    out = scn.run_campaign(platforms, scenario_names=replays, **kw)
    dt = time.perf_counter() - t0
    delta = ctl.fleet_trace_counts()["stream"] - before
    cells = len(platforms) * len(techniques) * len(replays)
    rows = []
    for scen in replays:
        row = out["table"][platforms[0].name]
        rows.append((f"replay/{scen}", dt / cells / N_STEPS * 1e6,
                     f"prop={row['proposed'][scen]['power_gain']:.2f}x"
                     f";hyb={row['hybrid'][scen]['power_gain']:.2f}x"
                     f";qos={row['proposed'][scen]['qos_violation_rate']:.3f}"))
    rows.append(("replay/stream_reuse", None,
                 f"retraces={delta};chunk={chunk}"))
    for n, s in sorted(tr.bundled_sources().items()):
        rows.append((f"replay/source/{n}", None,
                     f"samples={s.n_samples};interval_s={s.interval_s:g}"
                     f";mean={s.utilization.mean():.3f}"))
    return rows


def bench_scheduler():
    """Per-tenant scheduling co-optimized with DVFS vs its ablations.

    Three arms on the ``multi_tenant`` scenario (three QoS classes:
    interactive / periodic / batch), one streaming campaign each:
    ``sched_dvfs`` (hybrid DVFS + priority scheduler — deferral shapes
    the gear argmin, valley-fill drains batch at the energy-optimal
    bin), ``dvfs_only`` (hybrid, scheduler off), and
    ``placement_only`` (priority scheduler placing onto gated nodes at
    nominal rails).  The co-optimized arm must win on power at
    equal-or-better worst-tenant QoS violation.  The two
    ``stream_reuse`` rows are the tenant-axis zero-retrace witnesses:
    after the first arm compiles the chunk program, scheduler-on/off
    sweeps and tenant-count sweeps (scenarios padded to a common
    width) must add no compiled programs.
    """
    from repro.core import scenarios as scn
    platforms = [ctl.fpga_platform(ACCELERATORS["tabla"])]
    chunk = max(min(N_STEPS, 512), 1)
    kw = dict(scenario_names=("multi_tenant",), n_steps=N_STEPS,
              chunk_size=chunk, tenants=3)
    arms = (("sched_dvfs", "hybrid", "priority"),
            ("dvfs_only", "hybrid", "none"),
            ("placement_only", "power_gating", "priority"))
    cells = {}
    rows = []
    stream0 = None
    for label, tech, sched in arms:
        t0 = time.perf_counter()
        out = scn.run_campaign(platforms, techniques=(tech,),
                               scheduler=sched, **kw)
        dt = time.perf_counter() - t0
        c = out["table"][platforms[0].name][tech]["multi_tenant"]
        cells[label] = c
        if stream0 is None:
            stream0 = ctl.fleet_trace_counts()["stream"]
        rows.append((f"scheduler/{label}", dt / N_STEPS * 1e6,
                     f"power_w={c['mean_power_w']:.2f}"
                     f";worst_tenant_qos="
                     f"{c['worst_tenant_qos_violation']:.3f}"
                     f";t_viol=" + "/".join(
                         f"{v:.3f}" for v in c["tenant_qos_violation_rate"])
                     + ";t_starve=" + "/".join(
                         f"{v:.3f}" for v in c["tenant_starvation_rate"])))
    # Scheduler-on/off sweeps above share one chunk program; a
    # tenant-count sweep at a padded common width must reuse it too
    # (different T recompiles once, then 2- and 3-class scenarios ride
    # the same width-4 program).
    onoff_delta = ctl.fleet_trace_counts()["stream"] - stream0
    scn.run_campaign(platforms, techniques=("hybrid",),
                     scenario_names=("multi_tenant",), n_steps=N_STEPS,
                     chunk_size=chunk, tenants=4, scheduler="priority")
    before = ctl.fleet_trace_counts()["stream"]
    scn.run_campaign(platforms, techniques=("hybrid",),
                     scenario_names=("flash_crowd",), n_steps=N_STEPS,
                     chunk_size=chunk, tenants=4, scheduler="priority")
    width_delta = ctl.fleet_trace_counts()["stream"] - before
    s, d, p = (cells[k] for k in ("sched_dvfs", "dvfs_only",
                                  "placement_only"))
    rows.append(("scheduler/cooptimization", None,
                 f"power_vs_dvfs_only="
                 f"{s['mean_power_w'] / d['mean_power_w']:.3f}"
                 f";power_vs_placement_only="
                 f"{s['mean_power_w'] / p['mean_power_w']:.3f}"
                 f";qos_ok={int(s['worst_tenant_qos_violation'] <= d['worst_tenant_qos_violation'] + 1e-9 and s['worst_tenant_qos_violation'] <= p['worst_tenant_qos_violation'] + 1e-9)}"))
    rows.append(("scheduler/stream_reuse_onoff", None,
                 f"retraces={onoff_delta};chunk={chunk}"))
    rows.append(("scheduler/stream_reuse_tenant_width", None,
                 f"retraces={width_delta};chunk={chunk};width=4"))
    return rows


def bench_voltage_optimizer():
    """Runtime cost of the §V voltage selection (table build + lookup)."""
    plat = ctl.fpga_platform(ACCELERATORS["tabla"])
    grids = volt.VoltageGrids.default()
    point_us = _timeit(lambda: volt.optimize_point(
        plat.delay_fn, plat.power_fn, jnp.asarray(0.5), grids
    ).power.block_until_ready())
    levels = volt.bin_frequency_levels(25, 0.05)
    table_us = _timeit(lambda: volt.build_operating_table(
        plat.delay_fn, plat.power_fn, levels, grids).power
        .block_until_ready(), n=3)
    table = volt.build_operating_table(plat.delay_fn, plat.power_fn, levels,
                                       grids)
    lookup_us = _timeit(lambda: table.lookup(jnp.asarray(0.37))
                        .power.block_until_ready())
    return [("voltage_opt/grid_point", point_us, "13x19_grid"),
            ("voltage_opt/table_build_25bins", table_us, "synthesis_time"),
            ("voltage_opt/runtime_lookup", lookup_us, "runtime_path")]


def bench_composition():
    """Fleet-composition search: candidate mixes × scenarios, one sweep.

    The whole candidate batch rides the same two compiled fleet programs
    (run in two halves — the second half must not retrace).  Reports the
    per-cell cost and the per-scenario Pareto-set sizes.
    """
    from repro.core import composition as comp
    platforms = [ctl.fpga_platform(ACCELERATORS[n])
                 for n in ("tabla", "stripes")]
    scenarios = ("burse", "diurnal")
    cand = comp.enumerate_candidates(len(platforms), 6, 48)
    t0 = time.perf_counter()
    res = comp.search_fleet_composition(
        platforms, cand, scenarios, n_steps=N_STEPS,
        chunk_size=max(min(N_STEPS, 512), 1))
    dt = time.perf_counter() - t0
    cells = cand.shape[0] * len(platforms) * len(scenarios)
    pareto = ";".join(f"pareto_{s}={len(res.pareto[s])}" for s in scenarios)
    rows = [("composition/sweep", dt / cells * 1e6,
             f"cands={cand.shape[0]};{pareto}"
             f";retraces={res.retraces_second_half}")]
    for i, s in enumerate(scenarios):
        # Knee of the front: cheapest-power mix that still holds QoS
        # (falls back to the least-violating point if none does).
        idx = res.pareto[s]
        ok = [j for j in idx if res.qos_violation_rate[j, i] <= 0.25]
        j = ok[0] if ok else min(idx,
                                 key=lambda j: res.qos_violation_rate[j, i])
        rows.append((f"composition/knee/{s}", None,
                     "mix=" + "x".join(str(int(v))
                                       for v in res.candidates[j])
                     + f";power_w={res.total_power_w[j, i]:.1f}"
                     f";qos_viol={res.qos_violation_rate[j, i]:.3f}"))
    return rows


def bench_tpu_serving():
    """TPU adaptation: controller on *measured* roofline terms per arch."""
    path = os.path.join(os.path.dirname(__file__), "dryrun_results.jsonl")
    rows = []
    if not os.path.exists(path):
        return [("tpu_serving/skipped", None, "no dryrun_results.jsonl")]
    cells = [json.loads(l) for l in open(path)]
    trace = _trace(512, seed=3)
    from repro.serving.autoscale import RooflineTerms, compare_techniques
    seen = set()
    for r in cells:
        if (r["status"] != "ok" or r["mesh"] != "16x16"
                or r["shape"] not in ("decode_32k", "train_4k")):
            continue
        key = (r["arch"], r["shape"])
        if key in seen:
            continue
        seen.add(key)
        rf = r["roofline"]
        terms = RooflineTerms(rf["t_compute_s"], rf["t_memory_s"],
                              rf["t_collective_s"])
        out = compare_techniques(terms, trace)
        g = {k: v.power_gain for k, v in out.items()}
        rows.append((f"tpu_serving/{r['arch']}/{r['shape']}", None,
                     f"prop={g['proposed']:.2f}x;core={g['core_only']:.2f}x"
                     f";hbm={g['bram_only']:.2f}x"
                     f";pg={g['power_gating']:.2f}x"
                     f";alpha_tpu={terms.alpha_tpu:.2f}"))
    return rows


# bench_fleet first: its per-cell-vs-batched comparison wants both paths
# measured from the same cold-start state.
BENCHES = [bench_fleet, bench_table2, bench_fig4_workload_sweep,
           bench_fig5_alpha_sweep, bench_fig6_beta_sweep, bench_fig10_trace,
           bench_fig12_per_accelerator_traces, bench_predictor,
           bench_hybrid, bench_campaign, bench_failure, bench_replay,
           bench_scheduler, bench_voltage_optimizer, bench_composition,
           bench_tpu_serving]


def main(argv=None) -> int:
    global N_STEPS
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=1024,
                    help="control-trace length (64 for the CI smoke)")
    ap.add_argument("--only", type=str, default="",
                    help="comma-separated substrings of bench names to run")
    ap.add_argument("--json", type=str, default=None,
                    help="machine-readable output path ('' to disable); "
                    "defaults to BENCH_fleet.json for full default runs "
                    "and off for --only/--steps subsets (so smoke runs "
                    "don't clobber the tracked perf record)")
    args = ap.parse_args(argv)
    N_STEPS = args.steps
    aot.enable_compilation_cache()
    only = [s for s in args.only.split(",") if s]
    if args.json is None:
        args.json = "" if (only or N_STEPS != 1024) else "BENCH_fleet.json"

    results = {}
    failed = []
    print("name,us_per_call,derived")
    for bench in BENCHES:
        if only and not any(s in bench.__name__ for s in only):
            continue
        try:
            for name, us, derived in bench():
                results[name] = {"us_per_call":
                                 None if us is None else round(us, 1),
                                 "derived": derived}
                us_s = "" if us is None else f"{us:.1f}"
                print(f"{name},{us_s},{derived}", flush=True)
        except Exception as e:  # noqa: BLE001
            failed.append(bench.__name__)
            results[bench.__name__] = {"us_per_call": None,
                                       "derived":
                                       f"ERROR:{type(e).__name__}:{e}"}
            print(f"{bench.__name__},nan,ERROR:{type(e).__name__}:{e}",
                  flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"steps": N_STEPS, "benches": results}, f, indent=1,
                      sort_keys=True)
        print(f"# wrote {args.json} ({len(results)} rows)", file=sys.stderr)
    if failed:
        print(f"# FAILED: {','.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
