"""Smoke run of the fleet campaign path on a TPU, through its entry points.

  python chip_smoke.py             # one chip: every phase below
  python chip_smoke.py --chips 4   # four chips: sharded vs unsharded only

One process drives the chip.  Phases (one chip):

1. device  — a TPU must be JAX's device (no CPU fallback); the tables
   program lowered at the campaign shape must hold the Pallas kernel
   (``tpu_custom_call``), not the lax reference.
2. campaign — ``scenarios.run_campaign`` over 5 FPGA accelerators × 6
   techniques × 15 scenarios (K = 450 cells) for 86,400 steps (one day
   at τ = 1 s), chunk 1024; then the same sweep on the tenant plane
   with the priority scheduler.  A 1024-step call of the same shapes
   compiles both programs first and is reported as set-up.
3. composition — ``composition.search_fleet_composition`` at the
   README quickstart size (1,000 candidates × 5 platforms × 2
   scenarios = 10,000 cells, 2,048 steps, chunk 512); the second
   candidate half must not retrace.
4. correctness — (a) the campaign's tables built on the chip by the
   kernel and by the reference agree to 1e-5; (b) a campaign slice run
   on the chip agrees with the same slice run on the host CPU.

``--chips 4`` runs only the aggregate campaign with the fleet axis
sharded over four chips (450 cells padded to 452) and unsharded, and
compares the two.  Any failure exits non-zero; the last line of stdout
is ``{"ok": true, "device": {...}}`` only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

FPGAS = ("tabla", "dnnweaver", "diannao", "stripes", "proteus")
N_SCENARIOS = 15
DAY_STEPS = 86_400            # one day of control at tau = 1 s
CHUNK = 1024
COMPOSE = dict(n_candidates=1000, max_nodes=8, scenarios=("burse", "diurnal"),
               n_steps=2048, chunk_size=512)
SLICE = dict(scenario_names=("burse", "node_failure", "replay_azure_vm_cpu"),
             n_steps=4096, chunk_size=1024)

#: Kernel vs reference tables: the bound of tests/test_kernels_grid_argmin.
TABLE_TOL = 1e-5
#: Chip vs host CPU (and sharded vs unsharded) campaign statistics.  One
#: control step of one cell whose discrete decision (bin, predictor
#: argmax) flips on a last-ulp difference moves its QoS rate or served
#: fraction by 1/4096 ≈ 2.4e-4 and its mean power by at most that share
#: of its power range; 1e-3 admits about four such flips per cell.
STAT_RTOL = {"mean_power_w": 1e-3}
STAT_ATOL = {"qos_violation_rate": 1e-3, "served_fraction": 1e-3}


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_check(n_chips: int):
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found — JAX's device is "
                 f"{d.platform!r}; this script does not fall back to it")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPU "
                 f"devices, JAX sees {len(devices)}")
    log(f"# device: {d.device_kind} x{len(devices)} (platform {d.platform})")
    return devices


def platforms():
    from repro.core import controller as ctl
    from repro.core.accelerators import ACCELERATORS
    return [ctl.fpga_platform(ACCELERATORS[n]) for n in FPGAS]


def kernel_in_tables_program() -> None:
    from repro.core import aot
    from repro.core import characterization as char
    from repro.core import controller as ctl
    params = char.stack_platform_params([p.params for p in platforms()])
    args = aot.tables_program_args(params, ctl.ControllerConfig())
    text = ctl._fleet_dvfs_tables_jit.lower(*args).compile().as_text()
    check("tpu_custom_call" in text,
          "tables program holds no tpu_custom_call: the Pallas kernel "
          "was not lowered")
    log(f"# tables program [P={args[0].watts_scale.shape[0]}, "
        f"R={args[1].shape[0]}, M={args[2].shape[1]}]: "
        "tpu_custom_call found")


def cell_stats(out, fields=("mean_power_w", "qos_violation_rate",
                            "served_fraction")):
    """{(platform, technique, scenario): {field: value}} of a campaign."""
    return {(p, t, s): {f: cell[f] for f in fields}
            for p, row in out["table"].items()
            for t, col in row.items() for s, cell in col.items()}


def check_campaign_table(out, n_cells: int, label: str) -> None:
    import math
    stats = cell_stats(out)
    check(len(stats) == n_cells,
          f"{label}: {len(stats)} cells, expected {n_cells}")
    for key, st in stats.items():
        check(math.isfinite(st["mean_power_w"]) and st["mean_power_w"] > 0,
              f"{label}: mean power of {key} is {st['mean_power_w']}")
        for f in ("qos_violation_rate", "served_fraction"):
            check(0.0 <= st[f] <= 1.0 + 1e-6,
                  f"{label}: {f} of {key} is {st[f]}")


def timed_campaign(label: str, n_steps: int, **kw):
    """Set-up call (compiles, 1 chunk) then the timed full-length run."""
    from repro.core import controller as ctl
    from repro.core import scenarios as scn
    plats = platforms()
    t0 = time.perf_counter()
    scn.run_campaign(plats, n_steps=CHUNK, chunk_size=CHUNK, **kw)
    setup_s = time.perf_counter() - t0
    traced = ctl.fleet_trace_counts()
    t0 = time.perf_counter()
    out = scn.run_campaign(plats, n_steps=n_steps, chunk_size=CHUNK, **kw)
    wall_s = time.perf_counter() - t0       # results are host floats
    check(ctl.fleet_trace_counts() == traced,
          f"{label}: the timed run retraced "
          f"({traced} -> {ctl.fleet_trace_counts()})")
    n_cells = len(plats) * len(out["techniques"]) * len(out["scenarios"])
    check(len(out["scenarios"]) == N_SCENARIOS,
          f"{label}: {len(out['scenarios'])} scenarios, "
          f"expected {N_SCENARIOS}")
    check_campaign_table(out, n_cells, label)
    log(f"# {label}: {n_cells} cells x {n_steps} steps, chunk {CHUNK}: "
        f"set-up {setup_s:.3f} s, wall {wall_s:.3f} s "
        f"({n_cells * n_steps / wall_s:.4g} cell-steps/s), "
        f"traces={ctl.fleet_trace_counts()}")
    return out


def composition_search() -> None:
    from repro.core import composition as comp
    from repro.core import controller as ctl
    import numpy as np
    plats = platforms()
    cand = comp.enumerate_candidates(len(plats), COMPOSE["max_nodes"],
                                     COMPOSE["n_candidates"], seed=0)
    n_cells = cand.shape[0] * len(plats) * len(COMPOSE["scenarios"])
    walls = []
    for _ in range(2):          # the first call compiles: set-up
        t0 = time.perf_counter()
        res = comp.search_fleet_composition(
            plats, cand, COMPOSE["scenarios"], n_steps=COMPOSE["n_steps"],
            chunk_size=COMPOSE["chunk_size"])
        walls.append(time.perf_counter() - t0)
        check(res.retraces_second_half == 0,
              f"composition: second half retraced "
              f"{res.retraces_second_half} program(s)")
        check(bool(np.all(np.isfinite(res.total_power_w))),
              "composition: non-finite total power")
        check(all(len(v) > 0 for v in res.pareto.values()),
              "composition: empty Pareto set")
    log(f"# composition: {n_cells} cells x {COMPOSE['n_steps']} steps, "
        f"chunk {COMPOSE['chunk_size']}: set-up {walls[0]:.3f} s, "
        f"wall {walls[1]:.3f} s, retraces_second_half=0, "
        f"pareto sizes {[len(v) for v in res.pareto.values()]}, "
        f"traces={ctl.fleet_trace_counts()}")


def tables_kernel_vs_ref() -> None:
    import numpy as np
    from repro.core import characterization as char
    from repro.core import controller as ctl
    from repro.kernels.grid_argmin import grid_argmin
    params = char.stack_platform_params([p.params for p in platforms()])
    grids, _, masks, levels = ctl._sweep_rows(ctl.ControllerConfig(),
                                              ctl.DEFAULT_TECHNIQUES)
    kern = ctl._fleet_dvfs_tables_jit(params, masks, levels, grids.core,
                                      grids.bram)
    ref = grid_argmin(params, masks, levels, grids.core, grids.bram,
                      impl="ref")
    worst = {}
    for f in ("v_core", "v_bram", "f_rel", "power"):
        a = np.asarray(getattr(kern, f), np.float64)
        b = np.asarray(getattr(ref, f), np.float64)
        worst[f] = float(np.max(np.abs(a - b) / (TABLE_TOL + TABLE_TOL
                                                  * np.abs(b))))
        check(worst[f] <= 1.0, f"tables: kernel vs reference {f} differs "
              f"beyond {TABLE_TOL} (worst/bound {worst[f]:.3g})")
    check(bool(np.array_equal(np.asarray(kern.feasible),
                              np.asarray(ref.feasible))),
          "tables: kernel vs reference feasibility differs")
    log(f"# check (a) tables kernel vs reference on chip, "
        f"shape {tuple(kern.power.shape)}: pass (worst |diff|/bound "
        f"{worst}, bound {TABLE_TOL} abs+rel)")


def compare_stats(a, b, label: str) -> dict:
    worst = {f: 0.0 for f in (*STAT_RTOL, *STAT_ATOL)}
    check(a.keys() == b.keys(), f"{label}: cell sets differ")
    for key in a:
        for f, rtol in STAT_RTOL.items():
            x, y = a[key][f], b[key][f]
            worst[f] = max(worst[f], abs(x - y) / max(abs(y), 1e-12))
            check(abs(x - y) <= rtol * abs(y),
                  f"{label}: {f} of {key}: {x} vs {y} (rtol {rtol})")
        for f, atol in STAT_ATOL.items():
            x, y = a[key][f], b[key][f]
            worst[f] = max(worst[f], abs(x - y))
            check(abs(x - y) <= atol,
                  f"{label}: {f} of {key}: {x} vs {y} (atol {atol})")
    return worst


def chip_vs_host() -> None:
    import jax
    from repro.core import controller as ctl
    from repro.core import scenarios as scn
    from repro.core.accelerators import ACCELERATORS
    tabla = [ctl.fpga_platform(ACCELERATORS["tabla"])]
    chip = scn.run_campaign(tabla, shard=False, **SLICE)
    with jax.default_device(jax.devices("cpu")[0]):
        host = scn.run_campaign(tabla, shard=False, **SLICE)
    worst = compare_stats(cell_stats(chip), cell_stats(host),
                          "check (b) chip vs host")
    log(f"# check (b) tabla x {len(ctl.DEFAULT_TECHNIQUES)} techniques x "
        f"{len(SLICE['scenario_names'])} scenarios x {SLICE['n_steps']} "
        f"steps, chip vs host CPU: pass (worst: mean_power_w rel "
        f"{worst['mean_power_w']:.3g}, qos_violation_rate abs "
        f"{worst['qos_violation_rate']:.3g}, served_fraction abs "
        f"{worst['served_fraction']:.3g})")


def sharded_vs_unsharded(n_chips: int) -> None:
    from repro.core import controller as ctl
    seen = []
    chunk_jit = ctl._fleet_stream_chunk_jit

    def spy(*args, **kw):      # record where the chunk program's inputs live
        seen.append((args[0].capacity.sharding, args[5].sharding,
                     args[5].shape))
        return chunk_jit(*args, **kw)

    ctl._fleet_stream_chunk_jit = spy
    try:
        sharded = timed_campaign("campaign sharded", DAY_STEPS, shard=True)
        tab_sh, chunk_sh, shape = seen[-1]
        seen.clear()
        unsharded = timed_campaign("campaign unsharded", DAY_STEPS,
                                   shard=False)
        one_sh = seen[-1][1]
    finally:
        ctl._fleet_stream_chunk_jit = chunk_jit
    for name, sh in (("tables", tab_sh), ("chunk", chunk_sh)):
        check(len(sh.device_set) == n_chips and not sh.is_fully_replicated,
              f"sharded {name} input spans {sorted(sh.device_set, key=str)}"
              f" (fully replicated: {sh.is_fully_replicated}), "
              f"expected {n_chips} devices")
    log(f"# sharded chunk input {shape} on "
        f"{sorted(str(d) for d in chunk_sh.device_set)}; unsharded on "
        f"{sorted(str(d) for d in one_sh.device_set)}")
    worst = compare_stats(cell_stats(sharded), cell_stats(unsharded),
                          "sharded vs unsharded")
    log(f"# sharded vs unsharded: pass (worst: {worst})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-vs-unsharded campaign")
    args = ap.parse_args(argv)

    # The CPU backend must stay reachable for the host reference check.
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    devices = device_check(args.chips)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import aot
    log(f"# compilation cache: {aot.enable_compilation_cache()}")

    phases = ([("sharded vs unsharded",
                lambda: sharded_vs_unsharded(args.chips))]
              if args.chips > 1 else
              [("tables kernel", kernel_in_tables_program),
               ("campaign", lambda: timed_campaign("campaign", DAY_STEPS)),
               ("tenant campaign", lambda: timed_campaign(
                   "tenant campaign", DAY_STEPS, tenants="auto",
                   scheduler="priority")),
               ("composition", composition_search),
               ("check (a)", tables_kernel_vs_ref),
               ("check (b)", chip_vs_host)])
    for name, phase in phases:
        t0 = time.perf_counter()
        phase()
        log(f"# phase {name}: {time.perf_counter() - t0:.3f} s")

    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
