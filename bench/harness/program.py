"""Drives the system under test: its entry points, with generated inputs.

The generated arrays are registered with ``scenarios.register_scenario``
under benchmark-owned names; each registered ``build``/``nodes``/
``tenants`` hook only returns the stored array, so the program receives
the generated inputs and runs none of its own generators.  The only other
thing taken from the program is a spy on ``controller.fleet_bin_tables``
that keeps a reference to the tables each call builds (no copy, no sync),
so the comparison can read what the timed call itself produced.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Sequence

import numpy as np

PREFIX = "bench."


def _stored(arr: np.ndarray) -> Callable:
    def build(n: int, rng) -> np.ndarray:
        if n > arr.shape[-1]:
            raise ValueError(f"stored traffic holds {arr.shape[-1]} steps, "
                             f"{n} asked for")
        return arr[..., :n]
    return build


def _stored_tenants(parts: np.ndarray, spec: dict) -> Callable:
    from repro.core import scheduler as sched_mod

    def build(n: int, rng):
        return (parts[:, :n], sched_mod.make_tenants(
            spec["priority"], spec["latency_target"], spec["share"]))
    return build


def register(scen: Sequence) -> List[str]:
    """Register generated scenarios; returns their names in the program."""
    from repro.core import scenarios as scn
    names = []
    for sc in scen:
        name = PREFIX + sc.name
        scn.register_scenario(scn.Scenario(
            name, "benchmark traffic (stored arrays)", _stored(sc.trace),
            nodes=None if sc.nodes is None else _stored(sc.nodes),
            tenants=(None if sc.tenants is None
                     else _stored_tenants(*sc.tenants))), overwrite=True)
        names.append(name)
    return names


def controller_kwargs(cfg: dict, mix: dict) -> dict:
    """``ControllerConfig`` keyword arguments stated by a configuration."""
    from repro.core import predictors as pred_mod
    c = cfg["controller"]
    pc = c["predictor"]
    return dict(
        n_nodes=c["n_nodes"], n_bins=c["n_bins"], margin=c["margin"],
        tau=c["tau"], f_floor=c["f_floor"],
        gated_power_frac=c["gated_power_frac"],
        predictor=pred_mod.PredictorConfig(
            kind=pc["kind"], policy=pc["policy"],
            update_mode=pc["update_mode"], count_decay=pc["count_decay"],
            warmup_steps=pc["warmup_steps"]),
        avail_predictor=c["avail_predictor"],
        scheduler=mix.get("scheduler", "none"))


@dataclasses.dataclass
class Entry:
    """One configured entry point: ``call()`` runs it once, ending in host
    results; ``tables`` holds the last call's §V tables (device arrays)."""

    call: Callable[[], object]
    warm: Callable[[], dict]
    n_cells: int
    n_steps: int
    chunk_size: int
    tables: list
    restore: Callable[[], None]


def platforms(cfg: dict):
    from repro.core import controller as ctl
    from repro.core.accelerators import ACCELERATORS
    return [ctl.fpga_platform(ACCELERATORS[n]) for n in cfg["platforms"]]


def spy_tables(store: list) -> Callable[[], None]:
    """Keep the tables every ``fleet_bin_tables`` call returns; the
    returned function undoes the spy."""
    from repro.core import controller as ctl
    real = ctl.fleet_bin_tables

    def spy(*args, **kw):
        out = real(*args, **kw)
        store[:] = [out]
        return out

    ctl.fleet_bin_tables = spy
    return lambda: setattr(ctl, "fleet_bin_tables", real)


def entry(cfg: dict, mix: dict, scen: Sequence,
          candidates: np.ndarray = None) -> Entry:
    from repro.core import aot
    from repro.core import characterization as char
    from repro.core import composition as comp
    from repro.core import controller as ctl
    from repro.core import scenarios as scn
    plats = platforms(cfg)
    names = register(scen)
    kw = controller_kwargs(cfg, mix)
    s, c = cfg["n_steps"], cfg["chunk_size"]
    params = char.stack_platform_params([p.params for p in plats])
    tables: list = []
    restore = spy_tables(tables)
    if cfg["entry"] == "campaign":
        techs = tuple(cfg["techniques"])
        n_cells = len(plats) * len(techs) * len(names)
        n_ten = 1
        if mix.get("tenants") is not None:
            n_ten = max(1 if sc.tenants is None else sc.tenants[0].shape[0]
                        for sc in scen)

        def call():
            return scn.run_campaign(plats, names, techs, n_steps=s, chunk_size=c,
                                    tenants=mix.get("tenants"), **kw)
        shape = (len(plats), len(techs), len(names))
    elif cfg["entry"] == "composition":
        techs = tuple(cfg["techniques"])
        n_cells = candidates.shape[0] * len(plats) * len(names)
        n_ten = 1
        budget = comp.CompositionBudget(
            reference_nodes=cfg["candidates"]["reference_nodes"])

        def call():
            return comp.search_fleet_composition(
                plats, candidates, names, budget, technique=techs[0],
                n_steps=s, chunk_size=c, **kw)
        half = -(-candidates.shape[0] // 2)
        shape = (half, len(plats), len(names))
    else:
        raise ValueError(f"unknown entry {cfg['entry']!r}")

    def warm():
        """AOT-compile both programs at this cell's shapes (one device;
        a sharded fleet compiles in the warm-up call instead)."""
        import jax
        if len(jax.devices()) > 1:
            return {}
        return aot.warm_fleet_programs(
            params, ctl.ControllerConfig(**kw), techs, fleet_shape=shape,
            chunk_size=c, n_tenants=n_ten)

    return Entry(call=call, warm=warm, n_cells=n_cells, n_steps=s,
                 chunk_size=c, tables=tables, restore=restore)
