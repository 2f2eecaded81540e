"""The control of a cell's check: the plain reference computed one step
below the configuration's stated precision, put in the program's place.

  python3 bench/control.py --workload <cell> --seeds 1 2 3 [--steps N]

The device arithmetic runs in bfloat16 (the configuration states float32)
and the long sums in float32 (it states float64).  For each seed it prints
the numbers the check compares, the control against the reference, each
beside the cell's limit: the control has to fail at least one of them.
It runs on the host only (NumPy); the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def readings(workload: str, seed: int, sizes: dict = None) -> dict:
    """The compared numbers of the bfloat16 control against the float32
    reference for one seed, at the cell's sizes (or ``sizes``)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import numpy as np
    import run_cell
    from harness import check
    from harness import traffic as traffic_gen
    spec = run_cell.resolve(workload)
    cfg, mix = spec["config"], spec["mix"]
    model = run_cell.reference(cfg)
    for k, v in (sizes or {}).items():
        cfg[k] = {**cfg[k], **v} if isinstance(v, dict) else v
    scen = traffic_gen.generate(mix, cfg["n_steps"], seed)
    if cfg["entry"] == "campaign":
        ref = model.campaign(cfg, mix, scen)
        ctl = model.campaign(cfg, mix, scen, model.bf16())
        prog = {k: np.asarray(ctl[k], np.float64) for k in (
            "mean_power_w", "qos_violation_rate", "served_fraction",
            "mean_backlog", "misprediction_rate")}
        if mix.get("tenants") is not None:
            for k in ("tenant_qos_violation_rate", "tenant_served_fraction"):
                prog[k] = np.asarray(ctl[k], np.float64)
        kind = "campaign"
    else:
        cc = cfg["candidates"]
        cand = traffic_gen.enumerate_candidates(
            len(cfg["platforms"]), cc["max_nodes"], cc["n_candidates"], seed)
        ref = model.composition(cfg, scen, cand)
        ctl = model.composition(cfg, scen, cand, model.bf16())
        prog = {k: np.asarray(ctl[k], np.float64) for k in (
            "total_power_w", "qos_violation_rate", "served_fraction")}
        kind = "composition"
    tabs = {f: np.asarray(ctl["tables"][f], np.float64)
            for f in ("power", "v_core", "v_bram")}
    return check.gaps(kind, prog, tabs, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--steps", type=int, default=None,
                    help="override the configuration's steps per call")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    import run_cell
    from harness import check
    limits = run_cell.resolve(args.workload)["limits"]
    sizes = {"n_steps": args.steps} if args.steps else None
    for seed in args.seeds:
        r = readings(args.workload, seed, sizes)
        judged = check.judge(r, limits)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": r,
                          "fails": sorted(k for k, j in judged.items()
                                          if not j["ok"])}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
