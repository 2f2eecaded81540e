"""The benchmark's one traffic generator.

A traffic mix is a data file (``bench/traffic/<mix>.json``) that lists
scenarios by name, each with a ``shape`` and its parameters; this module
turns a mix plus ``--seed`` into arrays.  The shapes are copies of the
program's scenario generators (``repro.core.workload``,
``repro.core.scenarios``, ``repro.core.traces``, ``repro.runtime.fault``)
as they stood when the benchmark was defined, so the yardstick cannot move
when the program's generators change.  Nothing here imports the program.

Per scenario the generator returns what the program's ``Scenario`` hooks
would return before the program's own post-processing:

* ``trace``  — the raw workload builder output ``[S]`` (clipped later);
* ``nodes``  — the alive-fraction schedule ``[S]`` or ``None``;
* ``tenants`` — ``(parts [T, S] float64, spec)`` or ``None``, where
  ``spec`` holds per-tenant ``priority``/``latency_target``/``share``.  A
  ``mix`` scenario's tenants are its components; a component may state
  its tenant class, ``"class": {"priority": p, "latency_target": l}``,
  and one that states none is priority 1, latency target 0.

A ``mix`` or ``splice`` component is a bundled sample (``{"sample":
file}``), a named scenario of the mix (``{"scenario": name}``, which is
also a cell of its own), or an inline shape (``{"shape": s, "params":
{...}}`` for ``s`` in ``INLINE_SHAPES``, or ``{"shape": "replay",
"sample": file}``), drawn from the component's stream exactly as a named
scenario of that entry would be, and no cell.

Every scenario draws from ``default_rng([seed, crc32(salt)])`` exactly as
the program salts its scenarios, so at a fixed seed the copies reproduce
the program's generators bit for bit (``bench/tests/test_traffic.py``).
"""

from __future__ import annotations

import dataclasses
import math
import os
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DATA_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic", "data")


# ---------------------------------------------------------------------------
# Self-similar and periodic generators (copy of repro.core.workload)
# ---------------------------------------------------------------------------


def _fgn(n: int, hurst: float, rng: np.random.Generator) -> np.ndarray:
    """Exact fractional Gaussian noise by circulant embedding."""
    if hurst == 1.0:
        return np.full(n, rng.standard_normal())
    k = np.arange(n)
    gamma = 0.5 * (np.abs(k + 1) ** (2 * hurst) - 2 * np.abs(k) ** (2 * hurst)
                   + np.abs(k - 1) ** (2 * hurst))
    row = np.concatenate([gamma, [0.0], gamma[1:][::-1]])
    eig = np.maximum(np.fft.fft(row).real, 0.0)
    m = row.size
    z = rng.standard_normal(m) + 1j * rng.standard_normal(m)
    x = np.fft.fft(np.sqrt(eig / (2.0 * m)) * z)
    out = np.sqrt(2.0) * x[:n].real
    std = out.std()
    return out / std if std > 0 else out


def bursty(n: int, seed: int, mean_load: float = 0.40, lam: float = 1000.0,
           hurst: float = 0.76, idc: float = 500.0,
           aggregate: int = 32) -> np.ndarray:
    """BURSE-style self-similar arrivals as fractions of peak."""
    rng = np.random.default_rng(seed)
    peak = lam / mean_load
    z = _fgn(n * aggregate, hurst, rng)
    arrivals = np.clip(lam + np.sqrt(idc * lam) * z, 0.0, peak)
    m = arrivals.mean()
    if m > 0:
        arrivals = np.clip(arrivals * (lam / m), 0.0, peak)
    if aggregate > 1:
        arrivals = arrivals.reshape(n, aggregate).mean(axis=1)
    return arrivals / peak


def periodic(n: int, period: int, mean_load: float, burst: float,
             seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    base = mean_load * (1.0 + 0.8 * np.sin(2 * np.pi * t / period))
    noise = burst * rng.standard_normal(n) * (rng.random(n) < 0.1)
    return np.clip(base + noise, 0.0, 1.0)


def _sub_seed(rng: np.random.Generator) -> int:
    return int(rng.integers(2 ** 31))


# ---------------------------------------------------------------------------
# Recorded samples (copy of repro.core.traces: load, normalize, replay)
# ---------------------------------------------------------------------------


def _normalize(util: np.ndarray) -> np.ndarray:
    util = np.asarray(util, np.float64)
    peak = float(util.max())
    if peak > 1.0:
        util = util / 100.0 if peak <= 100.0 else util / max(peak, 1e-12)
    return np.clip(util, 0.0, 1.0).astype(np.float32)


def load_sample(file: str) -> np.ndarray:
    """A bundled utilization sample as fractions (CSV: last column)."""
    path = os.path.join(DATA_DIR, file)
    if file.endswith(".csv"):
        data = np.genfromtxt(path, delimiter=",", names=True)
        util = np.atleast_1d(data[data.dtype.names[-1]])
    else:
        with np.load(path) as z:
            util = np.asarray(z["utilization"], np.float64)
    return _normalize(util)


def replay(base: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    """Phase-jittered looped replay of one sample per control step."""
    off = int(rng.integers(base.size))
    return base[(off % base.size + np.arange(n)) % base.size]


# ---------------------------------------------------------------------------
# Correlated failures (copy of repro.runtime.fault.FailureModel.sample)
# ---------------------------------------------------------------------------


def failure_fraction(n: int, rng: np.random.Generator, *, n_nodes: int,
                     n_racks: int, weibull_k: float, rack_fraction: float,
                     repair_mu: float, repair_sigma: float,
                     cascade_factor: float = 1.0, alive_floor: int = 1,
                     mttf_frac: float = 1.0 / 3.0) -> np.ndarray:
    """Alive fraction of a Weibull/lognormal rack+node failure process."""
    mttf = max(n * mttf_frac, 2.0)
    lam_rack = mttf / rack_fraction if rack_fraction > 0 else math.inf
    lam_node = mttf / (1.0 - rack_fraction) if rack_fraction < 1 else math.inf
    lam = np.asarray([lam_rack] * n_racks + [lam_node] * n_nodes, np.float64)
    racks = np.array_split(np.arange(n_nodes), n_racks)
    members = ([tuple(int(i) for i in r) for r in racks]
               + [(i,) for i in range(n_nodes)])
    n_ent = n_racks + n_nodes
    age = np.zeros(n_ent, np.float64)
    down_until = np.zeros(n_ent, np.int64)
    counts = np.empty(n, np.int64)
    k = weibull_k
    for t in range(n):
        down = down_until > t
        with np.errstate(divide="ignore", invalid="ignore"):
            h = (k / lam) * ((age + 1.0) / lam) ** (k - 1.0)
        h = np.where(np.isfinite(h), h, 0.0)
        if down.any():
            h = h * cascade_factor
        fail = (~down) & (rng.random(n_ent) < -np.expm1(-h))
        for e in np.flatnonzero(fail):
            dur = max(1, int(round(float(rng.lognormal(repair_mu,
                                                       repair_sigma)))))
            down_until[e] = t + dur
            age[e] = 0.0
        down = down_until > t
        age[~down] += 1.0
        dead = np.zeros(n_nodes, bool)
        for e in np.flatnonzero(down):
            dead[list(members[e])] = True
        counts[t] = n_nodes - int(dead.sum())
    return np.maximum(counts, alive_floor).astype(np.int32) / float(n_nodes)


def failure_windows(n: int, rng: np.random.Generator, per: int = 256,
                    lo: float = 0.2, hi: float = 0.5) -> np.ndarray:
    """Alive fraction: a few failure windows dropping 20-50 % of nodes."""
    frac = np.ones(n)
    for _ in range(max(1, n // per)):
        t0 = int(rng.integers(0, n))
        dur = int(rng.integers(max(n // 32, 2), max(n // 8, 4)))
        frac[t0:t0 + dur] -= rng.uniform(lo, hi)
    return np.clip(frac, 0.1, 1.0)


# ---------------------------------------------------------------------------
# Workload shapes (copies of the scenario builders)
# ---------------------------------------------------------------------------


def _flash_parts(n: int, rng: np.random.Generator):
    t = np.arange(n)
    base = 0.25 * (1.0 + 0.5 * np.sin(2 * np.pi * t / max(n // 4, 2)))
    steady = base + 0.02 * rng.standard_normal(n)
    crowd = np.zeros(n)
    for _ in range(max(1, n // 512)):
        t0 = int(rng.integers(0, n))
        amp = rng.uniform(0.5, 0.75)
        dur = max(8, n // 64)
        crowd[t0:] += amp * np.exp(-np.arange(n - t0) / dur)
    return [steady, crowd], None


def _multi_parts(n: int, rng: np.random.Generator):
    streams = [
        bursty(n, _sub_seed(rng), mean_load=0.5, hurst=0.8),
        periodic(n, max(n // 8, 2), 0.35, 0.2, _sub_seed(rng)),
        np.clip(0.2 + 0.05 * rng.standard_normal(n), 0.0, 1.0),
    ]
    weights = rng.dirichlet(np.full(len(streams), 2.0))
    return [w * s for w, s in zip(weights, streams)], weights


_PARTS = {"flash_crowd": _flash_parts, "multi_tenant": _multi_parts}
#: Shapes a mix or splice component may carry inline: one stream each.
INLINE_SHAPES = ("bursty", "periodic", "ramp", "decay", "replay")


def _child(rng: np.random.Generator) -> np.random.Generator:
    return np.random.default_rng(int(rng.integers(2 ** 31)))


@dataclasses.dataclass(frozen=True)
class ScenarioTraffic:
    """One scenario's generated inputs (see the module docstring)."""

    name: str
    trace: np.ndarray
    nodes: Optional[np.ndarray]
    tenants: Optional[Tuple[np.ndarray, Dict[str, List[float]]]]


class Generator:
    """Builds every scenario of a mix from its data entries."""

    def __init__(self, scenarios: Dict[str, dict]):
        self.scenarios = scenarios
        self._samples: Dict[str, np.ndarray] = {}

    def sample(self, file: str) -> np.ndarray:
        if file not in self._samples:
            self._samples[file] = load_sample(file)
        return self._samples[file]

    def rng(self, name: str, seed: int, salt: str = "") -> np.random.Generator:
        base = self.scenarios[name].get("salt", name)
        return np.random.default_rng([seed, zlib.crc32((base + salt).encode())])

    # -- workload ------------------------------------------------------------
    def _component(self, comp: dict, n: int, rng: np.random.Generator,
                   where: str) -> np.ndarray:
        """One component of a ``mix`` or ``splice``: an inline shape, a
        bundled sample, or a named scenario, whose clipped trace it is.
        An inline shape is drawn as a named scenario of that entry would
        be; it is no scenario of the mix, so it adds no cell."""
        if "shape" in comp:
            if comp["shape"] not in INLINE_SHAPES:
                raise ValueError(f"{where}: shape {comp['shape']!r} cannot "
                                 f"be inline; inline shapes are "
                                 f"{INLINE_SHAPES}")
            e = comp
        elif "sample" in comp:
            return replay(self.sample(comp["sample"]), n, rng)
        else:
            e = self.scenarios[comp["scenario"]]
        return np.clip(np.asarray(self._draw(e, n, rng, where), np.float32),
                       0.0, 1.0)

    def _draw(self, e: dict, n: int, rng: np.random.Generator, where: str):
        shape = e["shape"]
        if shape == "bursty":
            return bursty(n, _sub_seed(rng), **e.get("params", {}))
        if shape == "periodic":
            p = e["params"]
            return periodic(n, max(min(n, p["period_max"]), 2),
                            p["mean_load"], p["burst"], _sub_seed(rng))
        if shape == "ramp":
            p = e["params"]
            return np.linspace(p["lo"], p["hi"], n) + p["noise"] \
                * rng.standard_normal(n)
        if shape == "decay":
            p = e["params"]
            return (p["peak"] * np.exp(-np.arange(n) / max(n / 3.0, 1.0))
                    + p["floor"] + p["noise"] * rng.standard_normal(n))
        if shape in _PARTS:
            parts, _ = _PARTS[shape](n, rng)
            return sum(parts)
        if shape == "replay":
            return replay(self.sample(e["sample"]), n, rng)
        if shape == "mix":
            w = np.asarray(e["weights"], np.float64)
            w = w / w.sum()
            out = np.zeros(n, np.float64)
            for i, (wi, comp) in enumerate(zip(w, e["components"])):
                out += wi * np.asarray(self._component(
                    comp, n, _child(rng), f"{where} component {i}"),
                    np.float64)
            return np.clip(out, 0.0, 1.0).astype(np.float32)
        if shape == "splice":
            f = np.asarray(e["fractions"], np.float64)
            f = f / f.sum()
            edges = np.round(np.cumsum(np.concatenate([[0.0], f])) * n)
            edges = edges.astype(np.int64)
            edges[-1] = n
            segs = []
            for i, (comp, lo, hi) in enumerate(zip(e["components"],
                                                   edges[:-1], edges[1:])):
                child = _child(rng)
                if hi > lo:
                    segs.append(np.asarray(self._component(
                        comp, int(hi - lo), child,
                        f"{where} component {i}"), np.float32))
            return np.clip(np.concatenate(segs), 0.0, 1.0)
        raise ValueError(f"{where}: unknown shape {shape!r}")

    def _tenants(self, name: str, n: int, rng: np.random.Generator):
        e = self.scenarios[name]
        if "tenants" not in e and e["shape"] != "mix":
            return None
        if e["shape"] == "mix":
            w = np.asarray(e["weights"], np.float64)
            w = w / w.sum()
            parts = np.stack([wi * np.asarray(self._component(
                c, n, _child(rng), f"scenario {name!r} component {i}"),
                np.float64) for i, (wi, c) in enumerate(zip(
                    w, e["components"]))])
            cls = [c.get("class", {}) for c in e["components"]]
            return parts, _spec([c.get("priority", 1.0) for c in cls],
                                [c.get("latency_target", 0.0) for c in cls],
                                w)
        parts, weights = _PARTS[e["shape"]](n, rng)
        parts = np.stack([np.asarray(p, np.float64) for p in parts])
        cls = e["tenants"]
        if cls["share"] == "realized":
            means = np.maximum(np.clip(parts, 0.0, None).mean(-1), 1e-6)
            share = means / means.sum()
        else:
            share = weights
        return parts, _spec(cls["priority"], cls["latency_target"], share)

    def _nodes(self, name: str, n: int, rng: np.random.Generator):
        spec = self.scenarios[name].get("nodes")
        if spec is None:
            return None
        p = dict(spec)
        model = p.pop("model")
        if model == "windows":
            return failure_windows(n, rng, **p)
        if model == "weibull":
            return failure_fraction(n, rng, **p)
        raise ValueError(f"scenario {name!r}: unknown node model {model!r}")

    def build(self, name: str, n_steps: int, seed: int,
              tenants: bool) -> ScenarioTraffic:
        return ScenarioTraffic(
            name=name,
            trace=np.asarray(self._draw(self.scenarios[name], n_steps,
                                        self.rng(name, seed),
                                        f"scenario {name!r}")),
            nodes=self._nodes(name, n_steps, self.rng(name, seed, "/nodes")),
            tenants=(self._tenants(name, n_steps, self.rng(name, seed))
                     if tenants else None))


def _spec(priority: Sequence[float], latency: Sequence[float],
          share: Sequence[float]) -> Dict[str, List[float]]:
    """Tenant classes with ``share`` normalized as the program does."""
    sh = np.asarray(list(share), np.float64)
    sh = (sh / sh.sum()).astype(np.float32)
    return {"priority": [float(x) for x in priority],
            "latency_target": [float(x) for x in latency],
            "share": [float(x) for x in sh]}


def generate(mix: dict, n_steps: int, seed: int) -> List[ScenarioTraffic]:
    """Every scenario of a mix, in the mix's order, from ``seed``."""
    gen = Generator(mix["scenarios"])
    tenants = mix.get("tenants") is not None
    return [gen.build(name, n_steps, seed, tenants)
            for name in mix["scenarios"]]


def enumerate_candidates(n_platforms: int, max_nodes: int,
                         n_candidates: int, seed: int) -> np.ndarray:
    """``[N, P]`` unique non-empty node-count mixes (copy of
    ``repro.core.composition.enumerate_candidates``)."""
    space = (max_nodes + 1) ** n_platforms
    if space <= n_candidates + 1:
        grid = np.indices((max_nodes + 1,) * n_platforms)
        cand = grid.reshape(n_platforms, -1).T
        return cand[cand.sum(axis=1) > 0].astype(np.int64)
    rng = np.random.default_rng(seed)
    seen, out = set(), []
    while len(out) < n_candidates:
        draw = rng.integers(0, max_nodes + 1, size=(n_candidates, n_platforms))
        for row in draw:
            key = tuple(int(x) for x in row)
            if sum(key) == 0 or key in seen:
                continue
            seen.add(key)
            out.append(key)
            if len(out) == n_candidates:
                break
    return np.asarray(out, np.int64)
