"""The cells that were there before a cell could bring its mechanism as
data read exactly what they read before: their configuration as run,
their traffic arrays at the cell's own size, their reference tables, and
the reference's outputs on that traffic cut to a test's size hash to the
values read at the commit before the harness learned mix overrides,
reference modules by name and the ``headroom`` technique.

The hashes are of float32/float64 bytes as NumPy computes them on x86-64;
a different NumPy build may round a transcendental differently, which
this test would report as a move."""

import hashlib
import json

import numpy as np
import pytest

import run_cell
from harness import traffic as tg
from reference import model

SEED = 2 ** 31 + 2024
SMALL = {"n_steps": 512, "n_candidates": 40}

PINNED = {
    "fpga5-paper.aggregate": {
        "config": "dce9c9fab4daa7bf",
        "trace.burse": "2e63fb560cbfabe4",
        "trace.diurnal": "86e0ee917bfa7960",
        "trace.flash_crowd": "df696f9bcac18c34",
        "trace.ramp": "1c1370be69dbb409",
        "trace.decay": "4a38da4c820b1ed5",
        "trace.multi_tenant": "c481aba0b374e6e3",
        "trace.node_failure": "4287110f98340295",
        "nodes.node_failure": "5da317941c87d7d0",
        "trace.rack_failure": "be6214f5a91f78fa",
        "nodes.rack_failure": "0e98bf863953d3ef",
        "trace.cascade": "fb9b26b5f781835a",
        "nodes.cascade": "e6306561a801d77a",
        "trace.flaky_fleet": "cf3c1c876b7db45f",
        "nodes.flaky_fleet": "f2a68c663e42597c",
        "trace.replay_azure_vm_cpu": "6f10b2610ef4a5ac",
        "trace.replay_google_cluster": "c372f84af550aecd",
        "trace.cloud_mix": "85a36c0553159810",
        "trace.cloud_splice": "78ccd1f0e55ff34b",
        "trace.cloud_overlay": "9fac3fb2628c023c",
        "active": "0cf8eaae3d73e12c",
        "margin_misprediction_rate": "dc958f5d145c6103",
        "mean_avail_nodes": "7a80d6303216dd3a",
        "mean_backlog": "538d0ecebe3f5855",
        "mean_power_w": "ccf382c91e2653ec",
        "misprediction_rate": "320120c8bed81cc2",
        "power_gain": "6ad2c73f1c2aff6d",
        "qos_violation_rate": "388452da016a0ebb",
        "served_fraction": "b6885121e279f2ae",
        "tables.capacity": "f635e71c80cbfbc6",
        "tables.f_rel": "3a64e67ff87bfabe",
        "tables.gated_power": "47fe4baea99dfc3c",
        "tables.n_active": "a48f229306f34806",
        "tables.node_power": "05ec02862726e5a4",
        "tables.power": "712d7277c33c4d3a",
        "tables.v_bram": "60e459b17c95f3a0",
        "tables.v_core": "b49cd0c41f498619",
        "tenant_qos_violation_rate": "dc026edeeba68b02",
        "tenant_served_fraction": "87c09f4eae28a813",
    },
    "compose-fpga5.quickstart": {
        "config": "76779b5fd8ea8f22",
        "trace.burse": "8ff6a3ffbb2e8936",
        "trace.diurnal": "1ddb80079a963e10",
        "candidates": "3789415bf8e1538f",
        "qos_violation_rate": "27aecf3ef79e8263",
        "served_fraction": "685d168ea1b5cee8",
        "tables.capacity": "bdebdf8914a0d78e",
        "tables.f_rel": "bdebdf8914a0d78e",
        "tables.gated_power": "d6ff1f8f33cd51c6",
        "tables.n_active": "11203560003fc967",
        "tables.node_power": "2f9d22753aef72a5",
        "tables.power": "96e62cd028c00fb2",
        "tables.v_bram": "8953809c19c65e17",
        "tables.v_core": "035645f920a75e62",
        "total_power_w": "5a10b6f0be0bfa17",
    },
    "compose-fpga5.quickstart-4chip": {
        "config": "76779b5fd8ea8f22",
        "trace.burse": "8ff6a3ffbb2e8936",
        "trace.diurnal": "1ddb80079a963e10",
        "candidates": "3789415bf8e1538f",
        "qos_violation_rate": "27aecf3ef79e8263",
        "served_fraction": "685d168ea1b5cee8",
        "tables.capacity": "bdebdf8914a0d78e",
        "tables.f_rel": "bdebdf8914a0d78e",
        "tables.gated_power": "d6ff1f8f33cd51c6",
        "tables.n_active": "11203560003fc967",
        "tables.node_power": "2f9d22753aef72a5",
        "tables.power": "96e62cd028c00fb2",
        "tables.v_bram": "8953809c19c65e17",
        "tables.v_core": "035645f920a75e62",
        "total_power_w": "5a10b6f0be0bfa17",
    },
}


def _h(x) -> str:
    x = np.ascontiguousarray(np.asarray(x))
    return hashlib.sha256(str(x.dtype).encode() + str(x.shape).encode()
                          + x.tobytes()).hexdigest()[:16]


def readings(workload: str) -> dict:
    """Hashes of what a cell reads: configuration, traffic at its size,
    reference tables, and the reference's outputs at a small size."""
    spec = run_cell.resolve(workload)
    cfg, mix = spec["config"], spec["mix"]
    out = {"config": hashlib.sha256(json.dumps(
        cfg, sort_keys=True).encode()).hexdigest()[:16]}
    for sc in tg.generate(mix, cfg["n_steps"], SEED):
        out[f"trace.{sc.name}"] = _h(sc.trace)
        if sc.nodes is not None:
            out[f"nodes.{sc.name}"] = _h(sc.nodes)
    small = dict(cfg, n_steps=SMALL["n_steps"])
    scen = tg.generate(mix, small["n_steps"], SEED)
    if cfg["entry"] == "campaign":
        ref = model.campaign(small, mix, scen)
    else:
        cc = dict(cfg["candidates"], n_candidates=SMALL["n_candidates"])
        small["candidates"] = cc
        cand = tg.enumerate_candidates(len(cfg["platforms"]), cc["max_nodes"],
                                       cc["n_candidates"], SEED)
        out["candidates"] = _h(cand)
        ref = model.composition(small, scen, cand)
    for k, v in sorted(ref.items()):
        if k == "tables":
            for f, t in sorted(v.items()):
                out[f"tables.{f}"] = _h(t)
        else:
            out[k] = _h(v)
    return out


@pytest.mark.parametrize("workload", sorted(PINNED))
def test_cell_reads_what_it_read(workload):
    assert readings(workload) == PINNED[workload]
