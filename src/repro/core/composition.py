"""Lumos-style fleet-composition search (ROADMAP item: *which* platforms,
*how many* nodes, under a power/cost budget).

The paper tunes one fixed fleet; the natural provisioning question above
it — given a catalog of platforms and a demand forecast, what *mix* of
platforms and node counts should the fleet be built from? — is a sweep
over thousands of candidate fleets.  With the fused cold path
(``kernels.grid_argmin`` + ``core.aot``) that sweep is two compiled
programs, never a host loop:

* the **platform** axis of the candidate mixes is the ``P`` axis of the
  one masked grid-sweep program (``fleet_bin_tables`` — every platform's
  §V operating table from a single ``grid_argmin`` launch);
* the **candidate** and **scenario** axes ride the leading axes of the
  one streaming chunk program (``simulate_fleet_stream``), whose compiled
  shape is ``(K, C)`` with ``K = candidates × platforms × scenarios``;
* the **node counts** enter as *values*, not shapes: each
  (candidate, platform) cell prices its sub-fleet through the per-step
  availability input and the per-node table decomposition
  (``availability_point``), and each candidate's demand scale rides the
  trace values.  Ten or ten thousand candidates of the same batch shape
  reuse one compiled program — ``fleet_trace_counts()`` is the witness,
  and :func:`search_fleet_composition` runs its candidate batch in two
  equal halves so the second half *proves* zero retraces.

**Model.**  A candidate is a node-count vector ``n`` over the platform
catalog.  Demand is a scenario trace ``w_t`` (fraction of a *reference*
fleet's peak — ``budget.reference_nodes`` node-units); the candidate
serves it with total capacity ``cap = Σ_j n_j·thr_j``, split across its
homogeneous sub-fleets in proportion to their capacity, so every
sub-fleet sees the same utilization fraction ``u_t = w_t·ref/cap`` of
its own peak and runs the paper's §V control loop on it (node-failure
scenarios apply their availability *fraction* to every sub-fleet).
Candidates too small for the demand saturate and show up as QoS
violations / unserved work; oversized ones waste watts — the returned
per-scenario Pareto sets over (mean power, QoS violation rate, cost)
expose exactly that trade.  DVFS techniques only (``proposed``,
``core_only``, ``bram_only``, ``freq_only``): their per-node operating
points are node-count-independent, which is what lets counts be values
instead of shapes.  (Hybrid/power-gating gears quantize *on the node
count* — a per-candidate table shape — so they are rejected here.)
"""

from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import characterization as char
from repro.core import controller as ctl
from repro.core import scenarios as scn
from repro.core import spans

#: Techniques whose per-node §V operating points do not depend on the
#: fleet's node count (no node-count gears / active-set quantization).
COMPOSABLE_TECHNIQUES = ("proposed", "core_only", "bram_only", "freq_only")


@dataclasses.dataclass(frozen=True)
class CompositionBudget:
    """Feasibility gates + the demand reference for a composition search.

    ``reference_nodes`` pins the demand scale: a scenario workload of
    ``w_t = 1.0`` means "the full peak of ``reference_nodes`` reference
    nodes" (throughput 1.0 each).  ``max_cost`` / ``max_power_w`` drop
    candidates whose build cost / nominal power exceed the budget before
    the sweep runs (``None`` = unconstrained).
    """

    reference_nodes: float = 8.0
    max_cost: Optional[float] = None
    max_power_w: Optional[float] = None


class CompositionResult(NamedTuple):
    """Everything the Pareto report needs, all host numpy."""

    platform_names: Tuple[str, ...]
    scenario_names: Tuple[str, ...]
    candidates: np.ndarray          # [N, P] int node counts (budget-feasible)
    cost: np.ndarray                # [N] build cost (Σ n_j·cost_j)
    nominal_power_w: np.ndarray     # [N] nominal watts (Σ n_j·node_nom_j)
    total_power_w: np.ndarray       # [N, S] mean watts under each scenario
    qos_violation_rate: np.ndarray  # [N, S] capacity-weighted over sub-fleets
    served_fraction: np.ndarray     # [N, S]
    pareto: Dict[str, np.ndarray]   # scenario -> candidate indices (sorted
                                    #   by mean power) of the Pareto set
    n_rejected: int                 # candidates dropped by the budget gates
    retraces_second_half: int       # MUST be 0 — the zero-retrace witness


def enumerate_candidates(n_platforms: int, max_nodes: int,
                         n_candidates: int, seed: int = 0) -> np.ndarray:
    """Sample ``[N, P]`` node-count vectors in ``[0, max_nodes]``.

    Enumerates the full ``(max_nodes+1)^P`` lattice when it fits in
    ``n_candidates``; otherwise draws unique random mixes.  All-zero
    fleets are excluded.
    """
    space = (max_nodes + 1) ** n_platforms
    if space <= n_candidates + 1:
        grid = np.indices((max_nodes + 1,) * n_platforms)
        cand = grid.reshape(n_platforms, -1).T
        return cand[cand.sum(axis=1) > 0].astype(np.int64)
    rng = np.random.default_rng(seed)
    seen, out = set(), []
    while len(out) < n_candidates:
        draw = rng.integers(0, max_nodes + 1,
                            size=(n_candidates, n_platforms))
        for row in draw:
            key = tuple(int(x) for x in row)
            if sum(key) == 0 or key in seen:
                continue
            seen.add(key)
            out.append(key)
            if len(out) == n_candidates:
                break
    return np.asarray(out, np.int64)


def pareto_front(objectives: np.ndarray) -> np.ndarray:
    """Boolean mask of non-dominated rows (all objectives minimized).

    Row ``c`` is dominated iff some row is ≤ on every objective and <
    on at least one.
    """
    a = objectives[:, None, :]
    b = objectives[None, :, :]
    dominated = ((b <= a).all(-1) & (b < a).any(-1)).any(axis=1)
    return ~dominated


def search_fleet_composition(
        platforms: Sequence[ctl.PlatformSpec],
        candidates: np.ndarray,
        scenarios: Optional[Sequence[str]] = None,
        budget: Optional[CompositionBudget] = None,
        *, technique: str = "proposed", n_steps: int = 2048,
        chunk_size: int = 512, seed: int = 0,
        node_cost: Optional[Sequence[float]] = None,
        node_throughput: Optional[Sequence[float]] = None,
        **cfg_kwargs) -> CompositionResult:
    """Sweep candidate fleet mixes × scenarios; return Pareto sets.

    ``candidates`` is ``[N, P]`` node counts over ``platforms`` (see
    :func:`enumerate_candidates`); ``node_cost``/``node_throughput`` are
    per-platform vectors (default 1.0/node each).  The sweep is two
    compiled programs (one grid sweep, one streaming chunk program)
    whose jit shape key is the flattened fleet shape ``[K, C]`` —
    node counts enter as *values*, so any candidate batch of the same
    shape reuses the programs: the batch runs in two equal halves and
    ``retraces_second_half`` witnesses that the second half recompiled
    nothing.
    """
    with spans.span(spans.ENTRY_INPUTS):
        if technique not in COMPOSABLE_TECHNIQUES:
            raise ValueError(
                f"technique {technique!r} is not composition-safe: its "
                "per-node operating points depend on the fleet's node count "
                f"(choose from {COMPOSABLE_TECHNIQUES})")
        budget = CompositionBudget() if budget is None else budget
        counts = np.asarray(candidates, np.float32)
        if counts.ndim != 2 or counts.shape[1] != len(platforms):
            raise ValueError(f"candidates must be [N, {len(platforms)}] "
                             f"node counts; got {counts.shape}")
        if np.any(counts.sum(axis=1) <= 0):
            raise ValueError("candidates must keep at least one node")

        n_plat = len(platforms)
        thr = (np.ones(n_plat, np.float32) if node_throughput is None
               else np.asarray(node_throughput, np.float32))
        cost_vec = (np.ones(n_plat, np.float32) if node_cost is None
                    else np.asarray(node_cost, np.float32))
        params = char.stack_platform_params([p.params for p in platforms])
        cfg = ctl.ControllerConfig(technique=technique, **cfg_kwargs)
        node_nom_w = ctl.fleet_node_nominal_watts(params, cfg)     # [P]

        # Budget gates (host-side, before anything compiles).
        cand_cost = counts @ cost_vec
        cand_nom_w = counts @ node_nom_w.astype(np.float32)
        keep = np.ones(counts.shape[0], bool)
        if budget.max_cost is not None:
            keep &= cand_cost <= budget.max_cost + 1e-9
        if budget.max_power_w is not None:
            keep &= cand_nom_w <= budget.max_power_w + 1e-9
        n_rejected = int((~keep).sum())
        counts, cand_cost, cand_nom_w = (counts[keep], cand_cost[keep],
                                         cand_nom_w[keep])
        if counts.shape[0] == 0:
            raise ValueError("no candidate passed the budget gates")

    # One grid sweep builds every platform's per-node §V table [P, M];
    # per-candidate tables differ only in *values* (counts-scaled power,
    # counts-valued n_active), broadcast onto [half, P, N_scen, M].
    tabs = ctl.fleet_bin_tables(params, cfg, techniques=(technique,))
    with spans.span(spans.ENTRY_INPUTS):
        per_node = {f: jnp.asarray(getattr(tabs, f)[:, 0]) for f in tabs._fields}

        scen_names, scen_traces, scen_avail = scn.build_suite(
            scenarios, n_steps=n_steps, n_nodes=cfg.n_nodes, seed=seed)
        n_scen = len(scen_names)
        # Scenario availability as a *fraction* of the configured fleet, so
        # node-failure scenarios hit every candidate sub-fleet pro rata.
        frac_avail = (scen_avail / float(cfg.n_nodes)).astype(np.float32)

        # Each sub-fleet of candidate c sees utilization u_t = w_t·ref/cap_c
        # of its own peak (capacity-proportional demand split).
        cap_c = counts @ thr                                       # [N]
        scale = (budget.reference_nodes / cap_c).astype(np.float32)

        # Two equal halves: the second half must hit the compiled chunk
        # program from the first — the zero-retrace witness.  Odd batches
        # repeat the last candidate (dropped from the results below).
        n_real = counts.shape[0]
        if n_real % 2:
            counts = np.concatenate([counts, counts[-1:]])
            scale = np.concatenate([scale, scale[-1:]])
        half = counts.shape[0] // 2

    def run_half(counts_h: np.ndarray, scale_h: np.ndarray):
        with spans.span(spans.ENTRY_INPUTS):
            n_h = counts_h.shape[0]
            cnt = jnp.asarray(counts_h)[:, :, None, None]          # [n,P,1,1]
            shape = (n_h, n_plat, n_scen, cfg.n_bins)

            def cell(x):
                return jnp.broadcast_to(x[None, :, None, :], shape)

            cells = ctl.BinTables(
                capacity=cell(per_node["capacity"]),
                power=cell(per_node["node_power"]) * cnt,
                v_core=cell(per_node["v_core"]), v_bram=cell(per_node["v_bram"]),
                f_rel=cell(per_node["f_rel"]),
                n_active=jnp.broadcast_to(cnt, shape),
                node_power=cell(per_node["node_power"]),
                gated_power=jnp.zeros(shape),
                headroom=jnp.zeros(shape[:-1]))
            u = (scale_h[:, None, None, None]
                 * scen_traces[None, None, :, :]).astype(np.float32)
            avail = (counts_h[:, :, None, None]
                     * frac_avail[None, None, :, :]).astype(np.float32)
        fs = ctl.simulate_fleet_stream(cells, u, cfg,
                                       chunk_size=chunk_size, avail=avail)
        return fs  # per-cell fields [n, P, N_scen]

    fs_a = run_half(counts[:half], scale[:half])
    before = ctl.fleet_trace_counts()
    fs_b = run_half(counts[half:], scale[half:])
    after = ctl.fleet_trace_counts()
    retraces = sum(after[k] - before[k] for k in after)

    with spans.span(spans.ENTRY_RESULTS):
        def merge(field: str) -> np.ndarray:
            return np.concatenate([np.asarray(getattr(fs_a, field)),
                                   np.asarray(getattr(fs_b, field))])[:n_real]

        counts = counts[:n_real]
        mean_power = merge("mean_power_w")                  # [N, P, S]
        viol = merge("qos_violation_rate")
        served = merge("served_fraction")
        # Sub-fleet weights: capacity share (zero-count cells weigh nothing).
        w = (counts * thr[None, :]) / (counts @ thr)[:, None]      # [N, P]
        total_power = mean_power.sum(axis=1)                       # [N, S]
        qos = np.einsum("np,nps->ns", w, viol)
        served_w = np.einsum("np,nps->ns", w, served)

        pareto: Dict[str, np.ndarray] = {}
        for s, name in enumerate(scen_names):
            objs = np.stack([total_power[:, s], qos[:, s], cand_cost], axis=1)
            idx = np.flatnonzero(pareto_front(objs))
            pareto[name] = idx[np.argsort(total_power[idx, s])]

        return CompositionResult(
            platform_names=tuple(p.name for p in platforms),
            scenario_names=tuple(scen_names),
            candidates=counts.astype(np.int64), cost=cand_cost,
            nominal_power_w=cand_nom_w, total_power_w=total_power,
            qos_violation_rate=qos, served_fraction=served_w, pareto=pareto,
            n_rejected=n_rejected, retraces_second_half=int(retraces))
