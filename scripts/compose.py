"""Fleet-composition search CLI: which platforms, how many nodes?

Sweeps candidate fleet mixes (node-count vectors over a platform
catalog) × scenarios through the fused fleet engine — one grid-sweep
program, one streaming chunk program, zero host loops — and prints the
per-scenario Pareto set over (mean power, QoS violation rate, cost).

  PYTHONPATH=src python scripts/compose.py --candidates 1000
  PYTHONPATH=src python scripts/compose.py --platforms tabla,stripes,tpu \
      --scenarios burse,diurnal --max-nodes 12 --budget-cost 16
  PYTHONPATH=src python scripts/compose.py --candidates 200 --steps 8192 \
      --json compose.json

The candidate batch runs in two equal halves; the second half must hit
the first half's compiled chunk program.  ``--fail-on-retrace`` (used by
CI) exits non-zero if it does not — the zero-retrace witness.
"""

from __future__ import annotations

import argparse
import json
import time

from campaign import build_platforms, profiled  # sibling script

from repro.core import aot
from repro.core import composition as comp
from repro.core import controller as ctl
from repro.core import scenarios as scn


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--candidates", type=int, default=200,
                    help="number of candidate fleet mixes to evaluate")
    ap.add_argument("--max-nodes", type=int, default=8,
                    help="per-platform node-count ceiling")
    ap.add_argument("--platforms", type=str, default="tabla,stripes",
                    help="comma list of accelerator names, 'tpu', or 'all'")
    ap.add_argument("--scenarios", type=str, default="burse,diurnal",
                    help=f"comma list from {sorted(scn.SCENARIOS)}")
    ap.add_argument("--technique", type=str, default="proposed",
                    choices=comp.COMPOSABLE_TECHNIQUES)
    ap.add_argument("--steps", type=int, default=2048)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reference-nodes", type=float, default=8.0,
                    help="demand scale: w=1.0 means this many reference "
                    "nodes' worth of peak throughput")
    ap.add_argument("--budget-cost", type=float, default=None,
                    help="drop candidates whose build cost exceeds this")
    ap.add_argument("--budget-watts", type=float, default=None,
                    help="drop candidates whose nominal watts exceed this")
    ap.add_argument("--pareto-top", type=int, default=8,
                    help="rows of each Pareto set to print")
    ap.add_argument("--warm", action="store_true",
                    help="AOT-compile the fleet programs up front")
    ap.add_argument("--fail-on-retrace", action="store_true",
                    help="exit 1 if the second candidate half retraced "
                    "any fleet program (CI contract)")
    ap.add_argument("--json", type=str, default="",
                    help="write the full result table to this path")
    ap.add_argument("--profile", type=str, default="",
                    help="write a profiler trace of the timed run, with "
                    "the program's host spans, under this directory")
    args = ap.parse_args(argv)

    print(f"# compilation cache: {aot.enable_compilation_cache()}")

    platforms = build_platforms(args.platforms)
    scenario_names = tuple(s for s in args.scenarios.split(",") if s)
    cand = comp.enumerate_candidates(len(platforms), args.max_nodes,
                                     args.candidates, seed=args.seed)
    budget = comp.CompositionBudget(reference_nodes=args.reference_nodes,
                                    max_cost=args.budget_cost,
                                    max_power_w=args.budget_watts)

    if args.warm:
        from repro.core import characterization as char
        params = char.stack_platform_params([p.params for p in platforms])
        n_half = -(-cand.shape[0] // 2)
        aot.warm_fleet_programs(
            params, ctl.ControllerConfig(technique=args.technique),
            (args.technique,),
            fleet_shape=(n_half, len(platforms), len(scenario_names)),
            chunk_size=min(args.chunk, args.steps))

    with profiled(args.profile):
        t0 = time.perf_counter()
        res = comp.search_fleet_composition(
            platforms, cand, scenario_names, budget,
            technique=args.technique, n_steps=args.steps,
            chunk_size=args.chunk, seed=args.seed)
        dt = time.perf_counter() - t0
    if args.profile:
        print(f"# profiler trace under {args.profile}")

    n = res.candidates.shape[0]
    print(f"# {n} candidates ({res.n_rejected} over budget) × "
          f"{len(res.platform_names)} platforms × "
          f"{len(res.scenario_names)} scenarios × {args.steps} steps "
          f"in {dt:.2f}s")
    print(f"# traces={ctl.fleet_trace_counts()} — "
          f"second-half retraces: {res.retraces_second_half}\n")

    short = [p.split(":")[-1] for p in res.platform_names]
    for scen in res.scenario_names:
        idx = res.pareto[scen]
        print(f"== scenario: {scen} — Pareto set "
              f"({len(idx)} of {n} candidates) ==")
        print(f"{'mix (' + ','.join(short) + ')':24s} "
              f"{'power_w':>9s} {'qos_viol':>9s} {'served':>7s} "
              f"{'cost':>6s}")
        s = list(res.scenario_names).index(scen)
        for i in idx[:args.pareto_top]:
            mix = "×".join(str(int(x)) for x in res.candidates[i])
            print(f"{mix:24s} {res.total_power_w[i, s]:9.1f} "
                  f"{res.qos_violation_rate[i, s]:9.3f} "
                  f"{res.served_fraction[i, s]:7.3f} {res.cost[i]:6.1f}")
        if len(idx) > args.pareto_top:
            print(f"... {len(idx) - args.pareto_top} more")
        print()

    if args.json:
        out = {
            "platforms": list(res.platform_names),
            "scenarios": list(res.scenario_names),
            "candidates": res.candidates.tolist(),
            "cost": res.cost.tolist(),
            "nominal_power_w": res.nominal_power_w.tolist(),
            "total_power_w": res.total_power_w.tolist(),
            "qos_violation_rate": res.qos_violation_rate.tolist(),
            "served_fraction": res.served_fraction.tolist(),
            "pareto": {k: v.tolist() for k, v in res.pareto.items()},
            "retraces_second_half": res.retraces_second_half,
        }
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        print(f"# wrote {args.json}")

    if args.fail_on_retrace and res.retraces_second_half:
        print(f"ERROR: second candidate half retraced "
              f"{res.retraces_second_half} fleet program(s) — the "
              "composition sweep is supposed to be one compiled program")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
