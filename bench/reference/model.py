"""Plain reference of the fleet power controller, written apart from it.

Everything is derived from a configuration file (``bench/configs/``) and
the generated traffic: the accelerator characterization (paper Table I and
the delay/power library), the section V operating tables (a brute-force
search of the voltage grid), the control loop (the Markov or EWMA
workload predictor, bin selection, the ``headroom`` bump and its
availability forecaster, the availability clamp, tenant scheduler) and
the host-side reductions.  It imports nothing of the program.

Arithmetic runs in a chosen type ``dt`` (the configuration states float32
on the device) and the long sums in ``acc`` (float64 on the host, as the
program states).  The lower-precision control runs the same code with
``dt = bfloat16`` and ``acc = float32``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

EPS = 1e-9
RAIL_INDEX = {"core": 0, "bram": 1, "io": 2, "config": 2}
RESOURCES = ("logic", "routing", "dsp", "memory", "memory_l", "io", "config")


@dataclasses.dataclass
class Prec:
    """Arithmetic of one reference run: device type and host sum type."""

    dt: type = np.float32
    acc: type = np.float64

    def a(self, x):
        return np.asarray(x, dtype=np.float64).astype(self.dt)

    def c(self, x):
        return self.dt(x)


F32 = Prec()


def bf16() -> Prec:
    import ml_dtypes
    return Prec(dt=ml_dtypes.bfloat16, acc=np.float32)


# ---------------------------------------------------------------------------
# Characterization: fabric sizing, delay and power terms
# ---------------------------------------------------------------------------


def fabric_device(util: dict, fab: dict) -> dict:
    """Smallest square fabric (I/O on the perimeter) that fits the design."""
    per_tile = fab["io_pads_per_tile"] * fab["io_signals_per_pad"]

    def counts(w):
        tiles = w * w
        m9k = int(tiles * fab["tile_frac_m9k"])
        m144k = int(tiles * fab["tile_frac_m144k"])
        dsp = int(tiles * fab["tile_frac_dsp"])
        return dict(labs=tiles - m9k - m144k - dsp, dsps=dsp, m9ks=m9k,
                    m144ks=m144k, io=4 * w * per_tile)

    w = max(4, int(np.ceil(util["io"] / (4 * per_tile) / 4))
            if util["io"] else 4)
    while True:
        d = counts(w)
        if all(d[k] >= util[k] for k in ("io", "m9ks", "m144ks", "dsps",
                                           "labs")):
            return d
        w += 1


@dataclasses.dataclass
class Platform:
    """Delay and power terms of one accelerator on its fabric."""

    delay: List[tuple]    # (weight, vth, alpha, v0, rail)
    power: List[tuple]    # (rail, v0, dyn, stat, kappa)

    def delay_grid(self, vc, vb, p: Prec):
        out = 0
        for w, vth, al, v0, rail in self.delay:
            v = vc if rail == 0 else vb
            num = v / np.maximum(v - p.c(vth), p.c(1e-6)) ** p.c(al)
            den = p.c(v0) / (p.c(v0) - p.c(vth)) ** p.c(al)
            out = out + p.c(w) * (num / den)
        return out

    def power_split(self, vc, vb, p: Prec):
        """(dynamic part per unit frequency, static part) on the grid."""
        dyn, stat = 0, 0
        for rail, v0, d, s, k in self.power:
            v = vc if rail == 0 else vb if rail == 1 else p.c(v0)
            r = v / p.c(v0)
            dyn = dyn + p.c(d) * r ** p.c(2.0)
            stat = stat + p.c(s) * r * np.exp(p.c(k) * (v - p.c(v0)))
        return dyn, stat


def platform(charz: dict, name: str) -> Platform:
    acc = charz["accelerators"][name]
    util, lib, rails = acc["util"], charz["library"], charz["rails"]
    dev = fabric_device(util, charz["fabric"])
    alpha = charz["bram_alpha"]
    mix = acc["core_mix"]
    tot = sum(mix.values())
    delay = [((w / tot) / (1.0 + alpha), lib[n]["vth"], lib[n]["alpha"],
              rails["core"], 0) for n, w in mix.items()]
    mem = lib["memory"]
    delay.append((alpha / (1.0 + alpha), mem["vth"], mem["alpha"],
                  rails["bram"], 1))
    used_idle = {
        "logic": (util["labs"], dev["labs"] - util["labs"]),
        "routing": (util["labs"], dev["labs"] - util["labs"]),
        "dsp": (util["dsps"], dev["dsps"] - util["dsps"]),
        "memory": (util["m9ks"], dev["m9ks"] - util["m9ks"]),
        "memory_l": (util["m144ks"], dev["m144ks"] - util["m144ks"]),
        "io": (util["io"], dev["io"] - util["io"]),
        "config": (dev["labs"] + 8 * dev["dsps"] + 4 * dev["m9ks"], 0),
    }
    power = []
    for n in RESOURCES:
        r = lib[n]
        used, idle = (float(x) for x in used_idle[n])
        power.append((RAIL_INDEX[r["rail"]], rails[r["rail"]],
                      used * charz["activity"] * r["p_dyn0"],
                      (used + idle * r["p_stat_idle_frac"]) * r["p_stat0"],
                      r["kappa"]))
    return Platform(delay=delay, power=power)


# ---------------------------------------------------------------------------
# Section V operating tables
# ---------------------------------------------------------------------------

TABLE_FIELDS = ("capacity", "power", "v_core", "v_bram", "f_rel", "n_active",
                "node_power", "gated_power")


def rail_grid(v_min: float, v_max: float, step: float, p: Prec):
    n = int(np.floor((v_max - v_min) / step + 1e-9)) + 1
    return p.c(v_max) - p.c(step) * p.a(np.arange(n - 1, -1, -1))


def bin_levels(m: int, margin: float, f_floor: float, p: Prec):
    return np.clip((p.a(np.arange(m)) + p.c(1.0)) / p.c(m) + p.c(margin),
                   p.c(f_floor), p.c(1.0))


def best_points(plat: Platform, mask: np.ndarray, levels, vc, vb,
                slack_eps: float, p: Prec):
    """Minimum-power grid point meeting timing at each level (first
    row-major index on ties; the nominal corner when none does)."""
    delay = plat.delay_grid(vc[:, None], vb[None, :], p).reshape(-1)
    dyn, stat = plat.power_split(vc[:, None], vb[None, :], p)
    dyn, stat = dyn.reshape(-1), stat.reshape(-1)
    vcs = np.repeat(vc, vb.size)
    vbs = np.tile(vb, vc.size)
    msk = mask.reshape(-1)
    out = {"v_core": [], "v_bram": [], "power": []}
    for f in levels:
        stretch = p.c(1.0) / np.maximum(f, p.c(1e-6))
        ok = (delay <= stretch * p.c(1.0 + slack_eps)) & msk
        obj = dyn * f + stat
        if ok.any():
            i = int(np.argmin(np.where(ok, obj, np.inf)))
            pw = obj[i]
        else:
            i = vc.size * vb.size - 1
            pw = dyn[i] * f + stat[i]
        out["v_core"].append(vcs[i])
        out["v_bram"].append(vbs[i])
        out["power"].append(pw)
    return {k: np.asarray(v, dtype=p.dt) for k, v in out.items()}


def operating_tables(cfg: dict, p: Prec = F32) -> Dict[str, np.ndarray]:
    """Per-bin tables ``{field: [P, T, M]}`` for the configuration's
    platforms and techniques, and each technique's failure reserve
    ``reserve [P, T]``: ``headroom`` runs ``hybrid``'s rows and reserves
    the configuration's ``headroom_frac``; every other technique 0."""
    charz, ctl = cfg["characterization"], cfg["controller"]
    rails = charz["rails"]
    m, n = ctl["n_bins"], ctl["n_nodes"]
    pll_w = p.c((2 if ctl["pll_dual"] else 1) * ctl["p_pll"])
    stall = 0.0 if ctl["pll_dual"] else min(ctl["t_lock"] / ctl["tau"], 1.0)
    vc = rail_grid(rails["crash"], rails["core"], rails["v_step"], p)
    vb = rail_grid(rails["crash"], rails["bram"], rails["v_step"], p)
    levels = bin_levels(m, ctl["margin"], ctl["f_floor"], p)
    full = np.ones((vc.size, vb.size), bool)
    masks = {"proposed": full, "hybrid": full,
             "core_only": np.zeros_like(full), "bram_only":
             np.zeros_like(full), "freq_only": np.zeros_like(full)}
    masks["core_only"][:, -1] = True
    masks["bram_only"][-1, :] = True
    masks["freq_only"][-1, -1] = True
    gears = p.a(np.arange(1, n + 1))
    f_need = levels[None, :] * p.c(n) / gears[:, None]
    f_node = np.clip(f_need, p.c(ctl["f_floor"]), p.c(1.0))
    gear_ok = f_need <= p.c(1.0 + 1e-9)

    per_plat, node_nominal = [], []
    for name in cfg["platforms"]:
        plat = platform(charz, name)
        dyn, stat = plat.power_split(p.c(rails["core"]), p.c(rails["bram"]),
                                     p)
        nominal = dyn + stat
        w_scale = p.c(float(charz["watts_nominal"]) / float(nominal))
        nom_w = nominal * w_scale
        node_nominal.append(float(nom_w + pll_w))
        rows = {}
        for tech in cfg["techniques"]:
            if tech in ("proposed", "core_only", "bram_only", "freq_only"):
                pts = best_points(plat, masks[tech], levels, vc, vb,
                                  ctl["slack_eps"], p)
                node_w = pts["power"] * w_scale
                rows[tech] = dict(
                    capacity=levels * p.c(1.0 - stall),
                    power=(node_w + pll_w) * p.c(n),
                    v_core=pts["v_core"], v_bram=pts["v_bram"], f_rel=levels,
                    n_active=np.full(m, n, p.dt), node_power=node_w + pll_w,
                    gated_power=np.zeros(m, p.dt))
            elif tech in ("hybrid", "headroom"):
                g_pts = [best_points(plat, full, f_node[g], vc, vb,
                                     ctl["slack_eps"], p)
                         for g in range(n)]
                h_w = np.stack([q["power"] for q in g_pts]) * w_scale
                total = (gears[:, None] * (h_w + pll_w)
                         + (p.c(n) - gears[:, None])
                         * p.c(ctl["gated_power_frac"]) * nom_w)
                total = np.where(gear_ok, total, np.inf).astype(p.dt)
                gi = np.argmin(total, axis=0)
                cols = np.arange(m)
                f_sel = f_node[gi, cols]
                rows[tech] = dict(
                    capacity=(gears[gi] / p.c(n)) * f_sel * p.c(1.0 - stall),
                    power=total[gi, cols],
                    v_core=np.stack([q["v_core"] for q in g_pts])[gi, cols],
                    v_bram=np.stack([q["v_bram"] for q in g_pts])[gi, cols],
                    f_rel=f_sel, n_active=gears[gi],
                    node_power=h_w[gi, cols] + pll_w,
                    gated_power=np.full(m, p.c(ctl["gated_power_frac"])
                                        * nom_w, p.dt))
            elif tech == "power_gating":
                edges = (np.arange(m) + 1.0) / m
                n_act = p.a(np.minimum(np.ceil(edges * n), n))
                rows[tech] = dict(
                    capacity=n_act / p.c(n),
                    power=n_act * (nom_w + pll_w) + (p.c(n) - n_act)
                    * p.c(ctl["gated_power_frac"]) * nom_w,
                    v_core=np.full(m, p.c(rails["core"]), p.dt),
                    v_bram=np.full(m, p.c(rails["bram"]), p.dt),
                    f_rel=np.ones(m, p.dt), n_active=n_act,
                    node_power=np.full(m, nom_w + pll_w, p.dt),
                    gated_power=np.full(m, p.c(ctl["gated_power_frac"])
                                        * nom_w, p.dt))
            else:
                raise ValueError(f"the reference has no technique {tech!r}")
        per_plat.append(rows)
    out = {f: np.stack([np.stack([np.asarray(r[t][f], p.dt)
                                  for t in cfg["techniques"]])
                        for r in per_plat]) for f in TABLE_FIELDS}
    out["node_nominal_w"] = np.asarray(node_nominal)   # one node + PLLs
    frac = [ctl["headroom_frac"] if t == "headroom" else 0.0
            for t in cfg["techniques"]]
    out["reserve"] = np.tile(np.asarray(frac, np.float64),
                             (len(cfg["platforms"]), 1))
    return out


# ---------------------------------------------------------------------------
# Traffic as the program receives it: clip, availability, tenant planes
# ---------------------------------------------------------------------------


def usable_nodes(frac: Optional[np.ndarray], n_steps: int, n: int):
    """Usable nodes per step: alive nodes, rounded, and on degraded steps
    the largest grid of power-of-two groups the survivors can run."""
    if frac is None:
        return np.full(n_steps, n, np.int64)
    alive = np.minimum(n, np.maximum(1, np.round(np.clip(frac, 0, 1) * n)))
    prefer = 1 << (max(n, 1).bit_length() - 1)

    def grid(a):
        if a >= n:
            return a
        model = prefer
        while model > 1 and a // model < 1:
            model //= 2
        data, pw = a // model, 1
        while pw * 2 <= data:
            pw *= 2
        return pw * model

    return np.asarray([grid(int(a)) for a in alive], np.int64)


def tenant_plane(sc):
    """``(plane [S, T], priority, latency, share)`` of one scenario."""
    if sc.tenants is None:
        parts = np.clip(np.asarray(sc.trace, np.float32), 0, 1)[None]
        parts = parts.astype(np.float64)
        spec = {"priority": [1.0], "latency_target": [0.0], "share": [1.0]}
    else:
        parts, spec = sc.tenants
    parts = np.clip(np.asarray(parts, np.float64), 0.0, None)
    tot = parts.sum(0)
    parts = parts * np.where(tot > 1.0, 1.0 / np.maximum(tot, 1e-9), 1.0)
    return parts.T.astype(np.float32), spec


def campaign_inputs(cfg: dict, mix: dict, scen: Sequence):
    """Per-cell inputs of a campaign, cells ordered platform, technique,
    scenario: ``w [K, S, T]``, ``avail [K, S]``, tenant classes ``[K, T]``."""
    n = cfg["controller"]["n_nodes"]
    s = cfg["n_steps"]
    av = np.stack([usable_nodes(sc.nodes, s, n) for sc in scen])
    if mix.get("tenants") is None:
        w = np.stack([np.clip(np.asarray(sc.trace, np.float32), 0, 1)
                      for sc in scen])[..., None]
        specs = [{"priority": [1.0], "latency_target": [0.0],
                  "share": [1.0]}] * len(scen)
        act = np.ones((len(scen), 1))
    else:
        planes = [tenant_plane(sc) for sc in scen]
        t = max(pl.shape[1] for pl, _ in planes)
        w = np.stack([np.pad(pl, ((0, 0), (0, t - pl.shape[1])))
                      for pl, _ in planes])
        specs, act = [], np.zeros((len(scen), t))
        for i, (pl, sp) in enumerate(planes):
            k = pl.shape[1]
            act[i, :k] = 1.0
            specs.append({"priority": list(sp["priority"]) + [-1.0] * (t - k),
                          "latency_target": list(sp["latency_target"])
                          + [0.0] * (t - k),
                          "share": list(sp["share"]) + [0.0] * (t - k)})
    reps = len(cfg["platforms"]) * len(cfg["techniques"])
    spec = {f: np.tile(np.asarray([sp[f] for sp in specs], np.float32),
                       (reps, 1)) for f in ("priority", "latency_target",
                                            "share")}
    spec["active"] = np.tile(act.astype(np.float32), (reps, 1))
    return (np.tile(w, (reps, 1, 1)), np.tile(av.astype(np.float32),
                                              (reps, 1)), spec)


# ---------------------------------------------------------------------------
# The headroom bump and its availability forecaster
# ---------------------------------------------------------------------------


def avail_warmup(ctl: dict) -> int:
    """Warm-up of the availability forecaster as the configuration states
    it (``"avail_predictor": {"kind": "persistence", "warmup_steps": w}``):
    a persistence forecaster over ``n_nodes`` bins, the only family the
    reference has."""
    ap = ctl["avail_predictor"]
    if not (isinstance(ap, dict) and ap.get("kind") == "persistence"
            and "warmup_steps" in ap):
        raise ValueError(f"the reference runs the headroom bump only on a "
                         f"persistence forecaster with a stated warm-up, "
                         f"not {ap!r}")
    return int(ap["warmup_steps"])


def avail_forecast(last: np.ndarray, step: int, warm: int, n: int,
                   p: Prec) -> np.ndarray:
    """Forecast usable nodes ``â = (b + 1) / n · n`` clipped to ``[1, n]``,
    ``b`` the last observed bin; warm-up pins the top bin, so a cold
    forecaster assumes a healthy fleet."""
    b = np.full_like(last, n - 1) if step < warm else last
    return np.clip((p.a(b) + p.c(1.0)) / p.c(n) * p.c(n), p.c(1.0), p.c(n))


def avail_bin(a_t: np.ndarray, n: int, p: Prec) -> np.ndarray:
    """The bin the forecaster observes for ``a`` usable nodes:
    ``(a − 0.5) / n`` in ``n`` bins, so ``a`` nodes are bin ``a − 1``."""
    frac = (a_t - p.c(0.5)) / p.c(n)
    return np.clip(np.floor(frac * p.c(n)), 0, n - 1).astype(np.int64)


def headroom_bump(tab: Dict[str, np.ndarray], reserve: np.ndarray,
                  a_hat: np.ndarray, selected: np.ndarray,
                  b_agg: np.ndarray, n: int, m: int, p: Prec) -> np.ndarray:
    """Raise each reserving cell's bin to the lowest one whose delivery
    at the forecast availability covers the selected bin's level plus the
    carried backlog; delivery is planned for at most ``spare =
    ceil(frac · n − 1e-9)`` lost nodes.  Never below ``selected``; cells
    that reserve nothing keep ``selected``."""
    spare = p.a(np.ceil(reserve * n - 1e-9))
    a_res = np.clip(a_hat, p.c(n) - spare, p.c(n))
    needed = np.minimum((p.a(selected) + p.c(1.0)) / p.c(m) + b_agg,
                        np.max(tab["capacity"], axis=1))
    n_tab = tab["n_active"]
    delivered = tab["capacity"] * (np.minimum(n_tab, a_res[:, None])
                                   / np.maximum(n_tab, p.c(1.0)))
    covers = delivered >= (needed - p.c(EPS))[:, None]
    lowest = np.where(covers.any(axis=1), np.argmax(covers, axis=1), m - 1)
    return np.where(reserve > 0, np.maximum(selected, lowest), selected)


# ---------------------------------------------------------------------------
# The workload predictor: a predict and an observe function per family
# ---------------------------------------------------------------------------


def markov_init(k: int, m: int, pc: dict, p: Prec) -> dict:
    """Transition counts ``[K, M, M]`` (a 0.01 uniform prior plus the
    identity) and each cell's current bin, 0 before any observation."""
    counts = np.broadcast_to(p.c(0.01) * np.ones((m, m), p.dt)
                             + np.eye(m, dtype=p.dt), (k, m, m)).copy()
    return {"counts": counts, "cur": np.zeros(k, np.int64)}


def markov_predict(state: dict, m: int, pc: dict, p: Prec) -> np.ndarray:
    """The most counted successor of the current bin (first on ties)."""
    rows = np.arange(state["cur"].size)
    return np.argmax(state["counts"][rows, state["cur"]], axis=1)


def markov_observe(state: dict, w_agg: np.ndarray, actual: np.ndarray,
                   m: int, pc: dict, p: Prec) -> dict:
    """Count the transition from the current bin to the observed one."""
    rows = np.arange(actual.size)
    state["counts"][rows, state["cur"], actual] += p.c(1.0)
    state["cur"] = actual
    return state


def ewma_init(k: int, m: int, pc: dict, p: Prec) -> dict:
    """The smoothed workload level, 1.0 (peak) before any evidence."""
    return {"level": np.ones(k, p.dt)}


def ewma_predict(state: dict, m: int, pc: dict, p: Prec) -> np.ndarray:
    """The level's bin, ``floor(level · M)`` clipped to ``[0, M − 1]``."""
    return np.clip(np.floor(state["level"] * p.c(m)), 0,
                   m - 1).astype(np.int64)


def ewma_observe(state: dict, w_agg: np.ndarray, actual: np.ndarray,
                 m: int, pc: dict, p: Prec) -> dict:
    """``level ← level + α · (w − level)`` on the aggregate workload."""
    lvl = state["level"]
    state["level"] = (lvl + p.c(pc["ewma_alpha"]) * (w_agg - lvl)).astype(
        p.dt)
    return state


def workload_predictor(pc: dict):
    """``(init, predict, observe)`` of the family the configuration's
    ``predictor`` states, looked up by name when called, so a reference
    module run over this one replaces a family by defining its
    functions.  ``markov`` only with the paper's argmax policy, learning
    on every step with no decay; ``ewma`` with its stated ``ewma_alpha``."""
    kind = pc["kind"]
    if kind == "markov":
        if (pc.get("policy"), pc.get("update_mode"),
                pc.get("count_decay")) != ("argmax", "always", 1.0):
            raise ValueError(f"the reference's markov predictor is argmax, "
                             f"always, no decay, not {pc!r}")
        return markov_init, markov_predict, markov_observe
    if kind == "ewma":
        if "ewma_alpha" not in pc:
            raise ValueError("the reference's ewma predictor needs the "
                             "configuration to state ewma_alpha")
        return ewma_init, ewma_predict, ewma_observe
    raise ValueError(f"the reference has no predictor {kind!r}")


# ---------------------------------------------------------------------------
# The control loop over K independent cells
# ---------------------------------------------------------------------------


def control_loop(tab: Dict[str, np.ndarray], w: np.ndarray, avail: np.ndarray,
                 spec: Dict[str, np.ndarray], ctl: dict, scheduler: str,
                 chunk: int, p: Prec = F32,
                 reserve: Optional[np.ndarray] = None
                 ) -> Dict[str, np.ndarray]:
    """Run every cell's loop; ``tab`` fields are ``[K, M]``, ``w`` is
    ``[K, S, T]`` and ``avail`` ``[K, S]``; ``reserve [K]`` is each cell's
    ``headroom`` reserve (none: no cell reserves).  Sums restart each
    chunk of ``chunk`` steps and are added up in ``p.acc``."""
    k, s, t = w.shape
    m = ctl["n_bins"]
    n = ctl["n_nodes"]
    bumps = reserve is not None and bool(np.any(reserve > 0))
    if bumps:
        a_warm = avail_warmup(ctl)
        a_last = np.full(k, n - 1, np.int64)
    pc = ctl["predictor"]
    warm = pc["warmup_steps"]
    pred_init, pred_predict, pred_observe = workload_predictor(pc)
    margin_bins = int(np.floor(ctl["margin"] * m + 1e-9))
    on = scheduler != "none"
    use_prio = scheduler == "priority"
    mig = p.c(ctl["migration_cost"])
    c0, c1, eps = p.c(0.0), p.c(1.0), p.c(EPS)

    tab = {f: p.a(v) for f, v in tab.items()}
    w, avail = p.a(w), p.a(avail)
    act = p.a(spec["active"])
    share, prio = p.a(spec["share"]), p.a(spec["priority"])
    slack = p.a(spec["latency_target"]) * share
    defer_cap = p.c(0.8) * slack
    order = np.argsort(-(prio - p.c(1e9) * (c1 - act)), axis=1, kind="stable")
    inv = np.argsort(order, axis=1, kind="stable")
    rows = np.arange(k)
    eff_best = np.argmin(tab["power"] / np.maximum(tab["capacity"], eps),
                         axis=1)

    pstate = pred_init(k, m, pc, p)
    mispred = np.zeros(k, np.int64)
    margin_miss = np.zeros(k, np.int64)
    backlog = np.zeros((k, t), p.dt)
    place = np.zeros((k, t), p.dt)
    sums = {f: np.zeros(k, p.acc) for f in
            ("power", "viol", "backlog", "offered", "avail")}
    tsums = {f: np.zeros((k, t), p.acc) for f in
             ("viol", "starve", "served", "offered")}

    def take(x, i):
        return x[rows, i]

    def sort_(x):
        return np.take_along_axis(x, order, axis=1)

    def unsort(x):
        return np.take_along_axis(x, inv, axis=1)

    for s0 in range(0, s, chunk):
        part = {f: np.zeros(k, p.dt) for f in sums}
        tpart = {f: np.zeros((k, t), p.dt) for f in tsums}
        for step in range(s0, min(s0 + chunk, s)):
            w_t, a_t = w[:, step], avail[:, step]
            w_agg = np.sum(w_t * act, axis=1).astype(p.dt)
            b_agg = np.sum(backlog * act, axis=1).astype(p.dt)
            if step < warm:
                predicted = np.full(k, m - 1)
            else:
                predicted = pred_predict(pstate, m, pc, p)
            actual = np.clip(np.floor(w_agg * p.c(m)), 0, m - 1).astype(int)
            selected = predicted
            if on:
                # The provisioned level: the predicted bin's upper edge.
                w_hat = (p.a(predicted) + c1) / p.c(m)
                d_hat = (w_hat[:, None] * share + backlog) * act
                defer = np.minimum(d_hat, defer_cap) * act
                target = np.clip(np.sum(d_hat - defer, axis=1), c0, c1)
                shaped = np.clip(np.floor(target.astype(p.dt) * p.c(m)), 0,
                                 m - 1).astype(int)
                gap = take(tab["capacity"], eff_best) - take(tab["capacity"],
                                                             shaped)
                bump = (b_agg >= gap) & (eff_best > shaped)
                selected = np.where(bump, eff_best, shaped)
            if bumps:
                a_hat = avail_forecast(a_last, step, a_warm, n, p)
                selected = headroom_bump(tab, reserve, a_hat, selected,
                                         b_agg, n, m, p)
                a_last = avail_bin(a_t, n, p)
            n_tab = take(tab["n_active"], selected)
            n_act = np.minimum(n_tab, a_t)
            cap = take(tab["capacity"], selected) * (n_act
                                                     / np.maximum(n_tab, c1))
            pwr = (n_act * take(tab["node_power"], selected)
                   + np.maximum(a_t - n_act, c0)
                   * take(tab["gated_power"], selected))

            d = (w_t + backlog) * act
            if on:
                d_adm = d - np.minimum(d, defer_cap) * act
                ds = sort_(d_adm)
                fill = np.minimum(np.maximum(
                    cap[:, None] - (np.cumsum(ds, axis=1).astype(p.dt) - ds),
                    c0), ds)
                adm_tot = np.sum(d_adm, axis=1).astype(p.dt)
                if use_prio:
                    alloc = unsort(fill)
                else:
                    alloc = (np.minimum(cap, adm_tot)[:, None] * d_adm
                             / np.maximum(adm_tot, eps)[:, None])
                deferred = d - d_adm
                spare = np.maximum(cap - np.sum(alloc, axis=1).astype(p.dt),
                                   c0)
                if use_prio:
                    dd = sort_(deferred)
                    drain = unsort(np.minimum(np.maximum(
                        spare[:, None]
                        - (np.cumsum(dd, axis=1).astype(p.dt) - dd), c0), dd))
                else:
                    def_tot = np.sum(deferred, axis=1).astype(p.dt)
                    drain = (np.minimum(spare, def_tot)[:, None] * deferred
                             / np.maximum(def_tot, eps)[:, None])
                alloc = alloc + drain
                needed = n_act[:, None] * alloc / np.maximum(cap, eps)[:, None]
                grow = np.maximum(needed - place - p.c(0.25), c0)
                loss = mig * grow * cap[:, None] / np.maximum(n_act, c1)[:, None]
                served = np.maximum(alloc - loss, c0)
                place = np.maximum(needed, place * p.c(0.95))
                due = np.sum(np.maximum(d - defer_cap, c0) * act, axis=1)
                violation = due.astype(p.dt) > cap + eps
            else:
                total = np.sum(d, axis=1).astype(p.dt)
                served_tot = np.minimum(cap, total)
                ratio = d / np.maximum(total, eps)[:, None]
                served = np.where((total > eps)[:, None],
                                  served_tot[:, None] * ratio,
                                  np.minimum(cap[:, None], d))
                violation = total > cap + eps
            backlog = (d - served).astype(p.dt)
            t_viol = (backlog > slack + eps) & (act > 0)
            t_starve = (d > p.c(1e-6)) & (served <= eps) & (act > 0)

            scored = step >= warm
            mispred += (predicted != actual) & scored
            margin_miss += (actual > predicted + margin_bins) & scored
            pstate = pred_observe(pstate, w_agg, actual, m, pc, p)

            part["power"] += pwr
            part["viol"] += violation.astype(p.dt)
            part["backlog"] += np.sum(backlog, axis=1).astype(p.dt)
            part["offered"] += w_agg
            part["avail"] += a_t
            tpart["viol"] += t_viol.astype(p.dt)
            tpart["starve"] += t_starve.astype(p.dt)
            tpart["served"] += served.astype(p.dt)
            tpart["offered"] += (w_t * act).astype(p.dt)
        for f in sums:
            sums[f] += part[f].astype(p.acc)
        for f in tsums:
            tsums[f] += tpart[f].astype(p.acc)

    final = np.asarray(backlog, p.acc)
    n_scored = max(s - warm, 1)
    return {
        "mean_power_w": sums["power"] / s,
        "qos_violation_rate": sums["viol"] / s,
        "served_fraction": (sums["offered"] - final.sum(1))
        / np.maximum(sums["offered"], 1e-9),
        "mean_backlog": sums["backlog"] / s,
        "mean_avail_nodes": sums["avail"] / s,
        "misprediction_rate": mispred / n_scored,
        "margin_misprediction_rate": margin_miss / n_scored,
        "tenant_qos_violation_rate": tsums["viol"] / s,
        "tenant_served_fraction": tsums["served"]
        / np.maximum(tsums["offered"], 1e-9),
        "active": np.asarray(act, np.float64) > 0,
    }


def campaign(cfg: dict, mix: dict, scen: Sequence, p: Prec = F32) -> dict:
    """Per-cell statistics ``{stat: [K]}`` and the tables of a campaign."""
    tabs = operating_tables(cfg, p)
    n_scen = len(scen)
    w, av, spec = campaign_inputs(cfg, mix, scen)
    flat = {f: np.repeat(tabs[f].reshape(-1, tabs[f].shape[-1]), n_scen,
                         axis=0) for f in TABLE_FIELDS}
    out = control_loop(flat, w, av, spec, cfg["controller"],
                       mix.get("scheduler", "none"), cfg["chunk_size"], p,
                       reserve=np.repeat(tabs["reserve"].reshape(-1), n_scen))
    nom = np.repeat(tabs["node_nominal_w"], len(cfg["techniques"]) * n_scen)
    out["power_gain"] = nom * out["mean_avail_nodes"] / out["mean_power_w"]
    out["tables"] = {f: tabs[f] for f in TABLE_FIELDS}
    return out


def composition(cfg: dict, scen: Sequence, candidates: np.ndarray,
                p: Prec = F32) -> dict:
    """``total_power_w``, ``qos_violation_rate``, ``served_fraction``
    ``[N, scenarios]`` of a composition search, plus its tables."""
    ctl = cfg["controller"]
    n_nodes, s = ctl["n_nodes"], cfg["n_steps"]
    tabs = operating_tables(cfg, p)
    per_node = {f: tabs[f][:, 0] for f in TABLE_FIELDS}       # [P, M]
    counts = candidates.astype(np.float64)
    n_c, n_p = counts.shape
    n_s = len(scen)
    traces = np.stack([np.clip(np.asarray(sc.trace, np.float32), 0, 1)
                       for sc in scen])
    frac = np.stack([usable_nodes(sc.nodes, s, n_nodes) for sc in scen]) \
        / float(n_nodes)
    scale = cfg["candidates"]["reference_nodes"] / counts.sum(1)   # [N]
    cnt = p.a(np.repeat(counts.reshape(-1), n_s))                 # [K]
    idx_p = np.tile(np.repeat(np.arange(n_p), n_s), n_c)
    tab = {f: per_node[f][idx_p] for f in TABLE_FIELDS}
    tab["n_active"] = np.repeat(cnt[:, None], ctl["n_bins"], axis=1)
    tab["power"] = tab["node_power"] * cnt[:, None]
    tab["gated_power"] = np.zeros_like(tab["capacity"])
    u = p.a(scale)[:, None, None, None] * p.a(traces)[None, None]
    u = np.broadcast_to(u, (n_c, n_p, n_s, s)).reshape(-1, s)[..., None]
    av = (p.a(counts)[:, :, None, None] * p.a(frac)[None, None])
    av = np.broadcast_to(av, (n_c, n_p, n_s, s)).reshape(-1, s)
    one = np.ones((u.shape[0], 1), np.float32)
    spec = {"priority": one, "latency_target": 0 * one, "share": one,
            "active": one}
    out = control_loop(tab, u, av, spec, ctl, "none", cfg["chunk_size"], p)
    mean_power = out["mean_power_w"].reshape(n_c, n_p, n_s)
    viol = out["qos_violation_rate"].reshape(n_c, n_p, n_s)
    served = out["served_fraction"].reshape(n_c, n_p, n_s)
    wgt = counts / counts.sum(1, keepdims=True)
    return {"total_power_w": mean_power.sum(1),
            "qos_violation_rate": np.einsum("np,nps->ns", wgt, viol),
            "served_fraction": np.einsum("np,nps->ns", wgt, served),
            "tables": {f: tabs[f] for f in TABLE_FIELDS}}
