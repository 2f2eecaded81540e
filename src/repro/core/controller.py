"""The central DVFS controller and platform simulation (paper §V, Fig. 9).

The paper's runtime loop per time step τ:

  workload counter → workload predictor (pluggable; paper: Markov chain)
  → frequency selector → voltage
  selector (a lookup into the per-frequency operating table precomputed at
  synthesis time) → PLL reprogram (dual-PLL hides the lock) → PMBUS rails.

We reproduce that loop exactly, as a jit-compiled ``lax.scan`` over the
workload trace, so thousand-step platform simulations take microseconds.
The *technique* (proposed joint scaling / core-only / bram-only / DFS /
power-gating / hybrid node-scaling+DVFS) only changes how the per-bin
operating table is built —
mirroring the paper's synthesis-time precomputation — while the runtime
loop is shared.

Power bookkeeping is in watts: the power model's arbitrary units are
scaled so a fully-utilized node at nominal voltage draws
``watts_nominal`` (paper: ≈20 W per FPGA).  PLL standing power/stall and
QoS backlog dynamics are included.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import characterization as char
from repro.core import pll as pll_mod
from repro.core import predictors as pred_mod
from repro.core import scheduler as sched_mod
from repro.core import spans
from repro.core import voltage as volt_mod
from repro.core.accelerators import Accelerator
from repro.kernels.grid_argmin import grid_argmin as grid_argmin_op
from repro.parallel import sharding as shd

Array = jax.Array

TECHNIQUES = ("proposed", "core_only", "bram_only", "freq_only",
              "power_gating", "nominal", "hybrid", "headroom")


# ---------------------------------------------------------------------------
# Platform abstraction (FPGA node or TPU chip)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlatformSpec:
    """One compute node's delay/power characterization.

    ``delay_fn(v_core, v_bram)`` — normalized critical-path / step delay
    (1.0 at nominal rails); ``power_fn(v_core, v_bram, f_rel)`` — node power
    in arbitrary units; ``watts_nominal`` pins the absolute scale.
    """

    name: str
    delay_fn: volt_mod.DelayFn
    power_fn: volt_mod.PowerFn
    nominal_power_arb: float
    watts_nominal: float = 20.0
    #: Array-parameterized twin of (delay_fn, power_fn) — required by the
    #: batched fleet path (``compare_all_batched`` / ``simulate_fleet``).
    params: Optional[char.PlatformParams] = None

    @property
    def watts_scale(self) -> float:
        return self.watts_nominal / self.nominal_power_arb

    def power_watts(self, v_core, v_bram, f_rel) -> Array:
        return self.power_fn(v_core, v_bram, f_rel) * self.watts_scale


def fpga_platform(acc: Accelerator, activity: float = 0.125,
                  watts_nominal: float = 20.0) -> PlatformSpec:
    """Paper's platform: one accelerator mapped on its smallest device."""
    pm = acc.power_model(activity)
    mix = dict(acc.core_mix or {}) or None
    return PlatformSpec(
        name=f"fpga:{acc.name}",
        delay_fn=volt_mod.fpga_delay_fn(acc.alpha, mix),
        power_fn=pm.power,
        nominal_power_arb=float(pm.nominal_power()),
        watts_nominal=watts_nominal,
        params=char.fpga_platform_params(acc.util, acc.device(), acc.alpha,
                                         mix, activity, watts_nominal),
    )


def analytic_platform(alpha: float = 0.2, beta: float = 0.4,
                      watts_nominal: float = 20.0) -> PlatformSpec:
    """The §III motivational model: Eq. 1-3 with free (α, β).

    Delay: (D_l(V_core) + α·D_m(V_bram)) / (1+α); power: core-rail mix
    plus ``β``-weighted BRAM power — used by the Fig. 4/5/6 sweeps.
    """
    logic = char.FPGA_LIBRARY["logic"]
    routing = char.FPGA_LIBRARY["routing"]
    mem = char.FPGA_LIBRARY["memory"]

    def power_fn(v_core, v_bram, f_rel):
        p_core = (0.4 * logic.total_power(v_core, f_rel)
                  + 0.6 * routing.total_power(v_core, f_rel))
        p_core = p_core / float(0.4 * logic.total_power(
            jnp.asarray(char.V_CORE_NOM), jnp.asarray(1.0))
            + 0.6 * routing.total_power(jnp.asarray(char.V_CORE_NOM),
                                        jnp.asarray(1.0)))
        p_mem = mem.total_power(v_bram, f_rel) / float(
            mem.total_power(jnp.asarray(char.V_BRAM_NOM), jnp.asarray(1.0)))
        return p_core + beta * p_mem

    return PlatformSpec(
        name=f"analytic:a{alpha}b{beta}",
        delay_fn=volt_mod.fpga_delay_fn(alpha),
        power_fn=power_fn,
        nominal_power_arb=1.0 + beta,
        watts_nominal=watts_nominal,
        params=char.analytic_platform_params(alpha, beta, watts_nominal),
    )


def tpu_platform(t_compute: float, t_memory: float, t_collective: float,
                 name: str = "tpu", composition: str = "max",
                 watts_nominal: float = 200.0) -> PlatformSpec:
    """TPU adaptation: roofline terms (seconds) from the compiled dry-run.

    The HBM frequency tracks the HBM domain and core/ICI track the core
    domain; per-step relative frequency applies to both domains (the
    controller slows the whole chip to match throughput, then the voltage
    optimizer splits the slack between domains — DESIGN.md §2).
    """
    chip = char.TpuChipPowerModel()

    def power_fn(v_core, v_hbm, f_rel):
        return chip.power(v_core, v_hbm, f_rel, f_rel)

    return PlatformSpec(
        name=f"tpu:{name}",
        delay_fn=volt_mod.tpu_delay_fn(t_compute, t_memory, t_collective,
                                       composition=composition),
        power_fn=power_fn,
        nominal_power_arb=float(chip.nominal_power()),
        watts_nominal=watts_nominal,
        params=char.tpu_platform_params(t_compute, t_memory, t_collective,
                                        composition, watts_nominal),
    )


# ---------------------------------------------------------------------------
# Controller configuration and per-bin operating tables
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    technique: str = "proposed"
    n_bins: int = 25
    margin: float = 0.05          # paper's t — additive, must exceed 1/M (§V)
    tau: float = 1.0              # time-step length (s)
    n_nodes: int = 8
    f_floor: float = 0.10         # lowest selectable relative frequency
    use_oracle: bool = False      # perfect prediction (upper bound; beyond paper)
    gated_power_frac: float = 0.0  # residual power of a power-gated node
    #: Predictor selection: a full ``PredictorConfig`` or just a
    #: registered kind name (``"markov"``, ``"ewma"``, …) — a bare
    #: string becomes ``PredictorConfig(kind=...)`` with defaults.
    predictor: pred_mod.PredictorConfig | str = dataclasses.field(
        default_factory=pred_mod.PredictorConfig)
    #: Availability forecaster for the ``headroom`` technique: a second
    #: predictor plane over the node schedule (``avail / n_nodes``),
    #: reusing the same ``core/predictors`` registry.  Resolved and
    #: bin-synced like ``predictor`` (``n_bins`` becomes ``n_nodes`` so
    #: bins map 1:1 onto usable-node counts).  The plane rides every
    #: cell's scan carry — which technique *acts* on the forecast is a
    #: traced table value, so headroom-on/off sweeps share one program.
    avail_predictor: pred_mod.PredictorConfig | str = "persistence"
    #: Failure depth the ``headroom`` technique provisions spare
    #: capacity for: the runtime bump plans delivery for up to
    #: ``ceil(frac·n_nodes)`` lost nodes — covering the forecast outage
    #: exactly while it is shallower, and refusing to chase deeper
    #: outages at full power (violations there are unavoidable anyway).
    #: Raising it trades power for QoS robustness.  The runtime loop
    #: reads the traced ``BinTables.headroom`` value, never this field.
    headroom_frac: float = 0.5
    #: Multi-tenant scheduler selection: a ``SchedulerConfig`` or a
    #: registered name (``"none"``, ``"priority"``, ``"fair_share"``) —
    #: a bare string is resolved through the ``core.scheduler`` registry.
    #: Only the streaming fleet path acts on it (the scheduler runs
    #: inside the ``[K, C]`` chunk scan); its knobs are traced *values*,
    #: so on/off sweeps share one compiled program.
    scheduler: sched_mod.SchedulerConfig | str = "none"
    pll: pll_mod.PllConfig = dataclasses.field(default_factory=pll_mod.PllConfig)
    v_step: float = char.V_STEP

    def __post_init__(self):
        if self.technique not in TECHNIQUES:
            raise ValueError(f"unknown technique {self.technique!r}")
        # Resolve the scheduler eagerly so a typo fails at config time
        # (mirrors the predictor-kind validation), keeping the field a
        # hashable SchedulerConfig for the static jit key.
        if isinstance(self.scheduler, str):
            object.__setattr__(self, "scheduler",
                               sched_mod.get(self.scheduler))
        elif not isinstance(self.scheduler, sched_mod.SchedulerConfig):
            raise TypeError(
                f"scheduler must be a registered name or SchedulerConfig, "
                f"got {type(self.scheduler).__name__}")
        if self.margin < 1.0 / self.n_bins + 1e-9:
            # §V: t must exceed 1/M so the capacity provisioned for bin i
            # still covers a one-bin under-prediction.
            raise ValueError(
                f"margin {self.margin} must exceed 1/n_bins = "
                f"{1.0 / self.n_bins:.4f} (paper §V: t > 1/M)")
        pcfg = self.predictor
        if isinstance(pcfg, str):
            pcfg = pred_mod.PredictorConfig(kind=pcfg)
        # Keep the predictor's bin grid and margin coverage in sync with
        # the controller: margin_bins = ⌊t·M⌋ is how many whole bins the
        # provisioned t% margin absorbs (≥ 1, since t > 1/M) — the
        # margin-aware score only charges misses beyond it.
        object.__setattr__(self, "predictor", dataclasses.replace(
            pcfg, n_bins=self.n_bins,
            margin_bins=int(np.floor(self.margin * self.n_bins + 1e-9))))
        if not 0.0 <= self.headroom_frac < 1.0:
            raise ValueError(f"headroom_frac {self.headroom_frac} must be "
                             "in [0, 1)")
        if int(np.ceil(self.headroom_frac * self.n_nodes - 1e-9)) \
                >= self.n_nodes:
            raise ValueError(
                f"headroom_frac {self.headroom_frac} plans for the whole "
                f"fleet lost (ceil(frac·{self.n_nodes}) = {self.n_nodes}) "
                "— the reserve must leave at least one planned node; "
                "lower it")
        acfg = self.avail_predictor
        if isinstance(acfg, str):
            acfg = pred_mod.PredictorConfig(kind=acfg)
        # The availability plane's bins are usable-node counts: bin b of
        # n_nodes covers fraction ((b, b+1]/n] — forecast_fraction maps
        # a predicted bin straight back to b+1 nodes.  No margin: the
        # spare gears ARE the margin.
        object.__setattr__(self, "avail_predictor", dataclasses.replace(
            acfg, n_bins=self.n_nodes, margin_bins=0))


class BinTables(NamedTuple):
    """Per-workload-bin operating points — the §V synthesis-time table.

    ``power`` is the fleet total at the *configured* ``n_nodes`` (the
    synthesis-time assumption).  The per-node decomposition
    ``node_power``/``gated_power`` lets the runtime loop re-price a step
    whose fleet lost nodes: with ``a`` nodes available the step draws
    ``min(n_active, a)·node_power + max(a - n_active, 0)·gated_power`` —
    dead nodes contribute nothing, and at full availability the
    decomposition reproduces ``power`` exactly
    (``power = n_active·node_power + (n_nodes - n_active)·gated_power``).

    ``headroom`` is a per-cell *scalar* (no bin axis): the spare-capacity
    fraction this cell's technique reserved at build time, 0 for every
    technique but ``headroom``.  The runtime loop keys its
    failure-anticipating bin bump on ``headroom > 0`` as a traced value,
    so headroom-on and -off cells share one compiled program.
    """

    capacity: Array   # [M] relative throughput delivered at this bin's point
    power: Array      # [M] platform power (watts) at this bin's point
    v_core: Array     # [M]
    v_bram: Array     # [M]
    f_rel: Array      # [M]
    n_active: Array   # [M] powered-on nodes at this bin's point
    node_power: Array   # [M] watts per powered-on node (incl. its PLLs)
    gated_power: Array  # [M] residual watts per gated-but-alive node
    headroom: Array     # [] per-cell reserved spare-capacity fraction


def _grids_for(technique: str, v_step: float) -> volt_mod.VoltageGrids:
    if technique in ("proposed", "hybrid", "headroom"):
        return volt_mod.VoltageGrids.default(v_step)
    if technique == "core_only":
        return volt_mod.VoltageGrids.core_only(v_step)
    if technique == "bram_only":
        return volt_mod.VoltageGrids.bram_only(v_step)
    if technique in ("freq_only", "nominal", "power_gating"):
        return volt_mod.VoltageGrids.frequency_only()
    raise ValueError(technique)


def nominal_node_watts(platform: PlatformSpec) -> float:
    """One node's watts at nominal rails and full frequency.

    Shared by the nominal/power-gating table builders and ``summarize`` —
    the denominator of the paper's power-reduction factor.
    """
    return float(platform.power_watts(jnp.asarray(char.V_CORE_NOM),
                                      jnp.asarray(char.V_BRAM_NOM),
                                      jnp.asarray(1.0)))


def pll_standing_watts(cfg: ControllerConfig) -> float:
    """Standing PLL power per node (two PLLs in the Fig. 9c architecture)."""
    return (2 if cfg.pll.dual else 1) * cfg.pll.p_pll


def _hybrid_gears(cfg: ControllerConfig) -> Tuple[Array, Array, Array]:
    """Node-count sweep cells for the hybrid technique.

    Gear ``g`` keeps ``g`` of ``n_nodes`` nodes powered on; to deliver a
    bin's provisioned level the active nodes must run at
    ``f_node = level·n/g`` — infeasible when that exceeds 1.  Returns
    ``(gears [G], f_node [G, M], feasible [G, M])``.
    """
    levels = volt_mod.bin_frequency_levels(cfg.n_bins, cfg.margin,
                                           cfg.f_floor)
    gears = jnp.arange(1, cfg.n_nodes + 1, dtype=jnp.float32)
    f_need = levels[None, :] * cfg.n_nodes / gears[:, None]
    f_node = jnp.clip(f_need, cfg.f_floor, 1.0)
    return gears, f_node, f_need <= 1.0 + 1e-9


def _headroom_spare(cfg: ControllerConfig) -> int:
    """Failure depth ``headroom`` provisions for: ``ceil(frac·n_nodes)``
    nodes' worth of spare capacity (the runtime bump plans delivery for
    up to that many lost nodes)."""
    return int(np.ceil(cfg.headroom_frac * cfg.n_nodes - 1e-9))


def build_bin_tables(platform: PlatformSpec, cfg: ControllerConfig) -> BinTables:
    """Precompute the optimal operating point for every workload bin."""
    m = cfg.n_bins
    pll_watts = pll_standing_watts(cfg)
    stall = pll_mod.stall_fraction(cfg.pll, cfg.tau)

    if cfg.technique == "nominal":
        cap = jnp.ones(m)
        node_w = nominal_node_watts(platform)
        power = jnp.full(m, (node_w + pll_watts) * cfg.n_nodes)
        return BinTables(capacity=cap, power=power,
                         v_core=jnp.full(m, char.V_CORE_NOM),
                         v_bram=jnp.full(m, char.V_BRAM_NOM),
                         f_rel=jnp.ones(m),
                         n_active=jnp.full(m, float(cfg.n_nodes)),
                         node_power=jnp.full(m, node_w + pll_watts),
                         gated_power=jnp.zeros(m),
                         headroom=jnp.asarray(0.0))

    if cfg.technique == "power_gating":
        # Conventional baseline (paper §III): scale the number of *active*
        # nodes linearly with predicted workload; active nodes run at
        # nominal V/f.  No extra margin — the bin's upper edge plus the
        # ceil already covers within-bin demand.
        edges = (np.arange(m) + 1.0) / m
        n_active = np.minimum(np.ceil(edges * cfg.n_nodes), cfg.n_nodes)
        cap = jnp.asarray(n_active / cfg.n_nodes)
        node_w = nominal_node_watts(platform)
        gated = (cfg.n_nodes - n_active) * cfg.gated_power_frac * node_w
        power = jnp.asarray(n_active * (node_w + pll_watts) + gated)
        return BinTables(capacity=cap, power=power,
                         v_core=jnp.full(m, char.V_CORE_NOM),
                         v_bram=jnp.full(m, char.V_BRAM_NOM),
                         f_rel=jnp.ones(m),
                         n_active=jnp.asarray(n_active, jnp.float32),
                         node_power=jnp.full(m, node_w + pll_watts),
                         gated_power=jnp.full(
                             m, cfg.gated_power_frac * node_w),
                         headroom=jnp.asarray(0.0))

    if cfg.technique in ("hybrid", "headroom"):
        # Joint node-scaling + DVFS: sweep how many nodes stay powered on
        # (a "gear") and jointly voltage-scale the active ones at the
        # gear's per-node frequency; gated nodes draw the residual
        # gated_power_frac.  Per bin, pick the gear minimizing total power.
        # ``headroom`` shares the same rows — its reserve is a *runtime*
        # policy (``_headroom_bump``), flagged by the headroom field.
        gears, f_node, gear_ok = _hybrid_gears(cfg)
        g_n = gears.shape[0]
        grids = _grids_for(cfg.technique, cfg.v_step)
        pts = volt_mod.optimize_batch(platform.delay_fn, platform.power_fn,
                                      f_node.reshape(-1), grids)
        node_w = (pts.power * platform.watts_scale).reshape(g_n, m)
        nom_w = nominal_node_watts(platform)
        total = (gears[:, None] * (node_w + pll_watts)
                 + (cfg.n_nodes - gears[:, None]) * cfg.gated_power_frac
                 * nom_w)
        total = jnp.where(gear_ok, total, jnp.inf)
        gi = jnp.argmin(total, axis=0)                        # [M]
        cols = jnp.arange(m)
        f_sel = f_node[gi, cols]
        return BinTables(
            capacity=(gears[gi] / cfg.n_nodes) * f_sel * (1.0 - stall),
            power=total[gi, cols],
            v_core=pts.v_core.reshape(g_n, m)[gi, cols],
            v_bram=pts.v_bram.reshape(g_n, m)[gi, cols],
            f_rel=f_sel, n_active=gears[gi],
            node_power=node_w[gi, cols] + pll_watts,
            gated_power=jnp.full(m, cfg.gated_power_frac * nom_w),
            headroom=jnp.asarray(cfg.headroom_frac
                                 if cfg.technique == "headroom" else 0.0))

    # DVFS techniques: joint / single-rail / frequency-only.
    levels = volt_mod.bin_frequency_levels(m, cfg.margin, cfg.f_floor)
    grids = _grids_for(cfg.technique, cfg.v_step)
    pts = volt_mod.optimize_batch(platform.delay_fn, platform.power_fn,
                                  levels, grids)
    node_w = pts.power * platform.watts_scale
    cap = levels * (1.0 - stall)
    power = (node_w + pll_watts) * cfg.n_nodes
    return BinTables(capacity=cap, power=power, v_core=pts.v_core,
                     v_bram=pts.v_bram, f_rel=levels,
                     n_active=jnp.full(m, float(cfg.n_nodes)),
                     node_power=node_w + pll_watts,
                     gated_power=jnp.zeros(m),
                     headroom=jnp.asarray(0.0))


# ---------------------------------------------------------------------------
# Trace simulation (the runtime loop)
# ---------------------------------------------------------------------------


class TraceResult(NamedTuple):
    power: Array            # [T] platform watts per step
    capacity: Array         # [T] delivered relative throughput
    violations: Array       # [T] bool — workload exceeded capacity
    backlog: Array          # [T] carried-over work (fraction of peak·τ)
    predicted_bin: Array    # [T]
    actual_bin: Array       # [T]
    v_core: Array           # [T]
    v_bram: Array           # [T]
    f_rel: Array            # [T]
    n_active: Array         # [T] powered-on nodes during the step
    mispredictions: Array   # scalar int — post-warmup exact-bin misses
    margin_misses: Array    # scalar int — post-warmup beyond-margin misses
    final_predictor: pred_mod.PredictorState


@dataclasses.dataclass(frozen=True)
class Summary:
    technique: str
    mean_power_w: float
    #: Nominal baseline of the *available* fleet: mean usable nodes ×
    #: per-node nominal watts.  Equals the configured-fleet baseline on
    #: healthy runs; strictly below it once nodes fail.
    nominal_power_w: float
    power_gain: float            # nominal / mean — the paper's headline metric
    qos_violation_rate: float
    served_fraction: float       # work served in-step / work offered
    misprediction_rate: float    # post-warmup mispredictions / post-warmup steps
    mean_backlog: float
    #: Post-warmup rate of predictions the controller's provisioned t%
    #: margin did NOT cover (actual bin > predicted + ⌊t·M⌋).  Exact-bin
    #: ``misprediction_rate`` charges the predictor for misses the
    #: margin absorbs by design; this is the honest "flying blind" rate.
    margin_misprediction_rate: float = float("nan")
    #: Measured request-latency QoS (closed-loop serving only; NaN for the
    #: open-loop modeled simulations, which have no per-request timeline).
    latency_p50: float = float("nan")
    latency_p99: float = float("nan")
    #: Configured-fleet baseline (``n_nodes`` × per-node nominal watts)
    #: and the gain against it.  On an availability-aware run the
    #: available-fleet ``power_gain`` is the honest efficiency metric —
    #: dead nodes draw nothing, so crediting the run with their nominal
    #: watts would overstate gains; ``power_gain_vs_configured`` keeps
    #: the fleet-as-provisioned comparison for capacity accounting.
    nominal_power_configured_w: float = float("nan")
    power_gain_vs_configured: float = float("nan")


class _StepOut(NamedTuple):
    """Per-step fields produced by one §V control step (scan ``ys``).

    The first ten fields are aggregate scalars (the emittable per-step
    :class:`TraceResult` fields); the ``tenant_*`` tail carries the
    ``[T]`` per-tenant outcome for the streaming reductions.
    """

    power: Array
    capacity: Array
    violation: Array
    backlog: Array
    predicted_bin: Array
    actual_bin: Array
    v_core: Array
    v_bram: Array
    f_rel: Array
    n_active: Array
    tenant_served: Array     # [T]
    tenant_backlog: Array    # [T]
    tenant_violation: Array  # [T] bool
    tenant_starved: Array    # [T] bool


#: Per-step fields ``emit=`` may request — aggregate scalars only (the
#: ``[T]``-shaped tenant tail concatenates on the wrong axis).
_EMITTABLE = ("power", "capacity", "violation", "backlog", "predicted_bin",
              "actual_bin", "v_core", "v_bram", "f_rel", "n_active")


def availability_point(tables: BinTables, selected,
                       avail_t) -> Tuple[Array, Array, Array]:
    """Clamp bin ``selected``'s operating point to ``avail_t`` usable
    nodes: returns ``(n_act, capacity, power)``.

    The single source of the §V availability pricing rule — shared by
    the scan's :func:`_control_step` (traced values) and the serving
    co-simulation's per-τ host loop (scalars): provisioned ``n_active``
    clamps to the survivors, delivered capacity rescales by
    ``n_act/n_active``, and power is re-priced from the per-node
    decomposition so dead nodes draw nothing while gated-but-*alive*
    nodes keep the gating residual.
    """
    n_tab = tables.n_active[selected]
    n_act = jnp.minimum(n_tab, avail_t)
    cap = tables.capacity[selected] * (n_act / jnp.maximum(n_tab, 1.0))
    pwr = (n_act * tables.node_power[selected]
           + jnp.maximum(avail_t - n_act, 0.0)
           * tables.gated_power[selected])
    return n_act, cap, pwr


_Carry = Tuple[pred_mod.PredictorState, pred_mod.PredictorState, Array,
               Array]


def _headroom_bump(tables: BinTables, cfg: ControllerConfig,
                   astate: pred_mod.PredictorState, selected: Array,
                   backlog_agg: Array) -> Array:
    """Failure-anticipating bin bump (the ``headroom`` runtime policy).

    Forecast next-step availability from the second predictor plane
    (``â`` usable nodes), then find the *lowest* bin whose
    availability-degraded delivery still covers the selected bin's
    demand plus carried backlog — pre-spinning to a higher gear before
    (and while) nodes are gone, and draining the backlog that otherwise
    keeps violating QoS long after repair.  The provisioning depth is
    bounded by the reserve: delivery is planned for at most
    ``ceil(headroom_frac·n_nodes)`` lost nodes, so shallow outages are
    covered exactly while deeper ones (where violations are unavoidable
    at any operating point) don't burn full fleet power.  Everything is
    traced; cells with ``tables.headroom == 0`` get their ``selected``
    back unchanged, so the one chunk program serves every technique.
    """
    m = cfg.n_bins
    a_hat = jnp.clip(pred_mod.forecast_fraction(cfg.avail_predictor, astate)
                     * cfg.n_nodes, 1.0, float(cfg.n_nodes))
    spare = jnp.ceil(tables.headroom * cfg.n_nodes - 1e-9)
    a_res = jnp.clip(a_hat, cfg.n_nodes - spare, float(cfg.n_nodes))
    needed = jnp.minimum((selected + 1.0) / m + backlog_agg,
                         jnp.max(tables.capacity))
    delivered = tables.capacity * (jnp.minimum(tables.n_active, a_res)
                                   / jnp.maximum(tables.n_active, 1.0))
    cand = jnp.where(delivered >= needed - 1e-9, jnp.arange(m), m)
    bump = jnp.minimum(jnp.min(cand), m - 1).astype(selected.dtype)
    # The bump only ever raises the bin — capacity plateaus (clipped top
    # levels) must not let it *lower* provisioning below the selection.
    return jnp.where(tables.headroom > 0,
                     jnp.maximum(selected, bump), selected)


def _control_step(tables: BinTables, cfg: ControllerConfig,
                  carry: _Carry, w_t: Array, avail_t: Array,
                  spec: sched_mod.TenantSpec, sched: Array
                  ) -> Tuple[_Carry, _StepOut]:
    """One §V control step: predict → schedule-shape → select → clamp to
    availability → place/serve → observe.

    Shared by the materializing scan and the streaming chunk scan.
    ``w_t`` is the step's per-tenant offered work ``[T]`` (aggregate
    callers pass a single default tenant); ``carry`` threads the
    workload and availability predictor states plus the per-tenant
    backlog and node-placement ``[T]`` arrays.  ``avail_t`` is the
    step's usable node count (``cfg.n_nodes`` for a healthy fleet);
    :func:`availability_point` clamps the selected bin's operating point
    to it, so dead nodes are unpowered and unprovisioned.

    The availability plane mirrors the workload one: a second
    ``PredictorState`` (``cfg.avail_predictor``) trains online on
    ``avail_t / n_nodes`` in *every* cell, and :func:`_headroom_bump`
    raises the provisioned bin for cells whose tables reserved headroom
    — a traced decision, so the plane costs no extra programs.

    The scheduler (``sched`` = :func:`~repro.core.scheduler
    .scheduler_values`) acts twice, both as traced values: it shapes
    the provisioned *bin* (defer slack-tolerant tenants, cover overdue
    backlog — :func:`~repro.core.scheduler.provision_bin`, the DVFS
    co-optimization) and it splits the delivered capacity across
    tenants (:func:`~repro.core.scheduler.schedule_step` — priority
    admission, node bin-packing, migration cost).  Disabled, both
    collapse to the aggregate controller: a step violates QoS when its
    *demand* — offered work plus carried backlog — exceeds delivered
    capacity, exactly the served-within-τ semantics the paper uses.
    """
    mstate, astate, backlog_t, place = carry
    w_agg = jnp.sum(w_t * spec.active, -1)
    backlog_agg = jnp.sum(backlog_t * spec.active, -1)
    predicted = pred_mod.predict(cfg.predictor, mstate)
    actual = pred_mod.workload_to_bin(w_agg, cfg.n_bins)
    base = jnp.where(cfg.use_oracle, actual, predicted)
    shaped = sched_mod.provision_bin(spec, base, backlog_t, cfg.n_bins)
    shaped = sched_mod.opportunistic_bin(
        tables.power, tables.capacity, shaped, backlog_agg)
    selected = jnp.where(sched[0] > 0, shaped, base)
    selected = _headroom_bump(tables, cfg, astate, selected, backlog_agg)

    n_act, cap, pwr = availability_point(tables, selected, avail_t)

    # QoS/backlog dynamics: offered work this step plus carried backlog,
    # served up to delivered capacity — allocated across tenants by the
    # scheduler (a proportional split when disabled).
    demand = w_t + backlog_t
    alloc = sched_mod.schedule_step(spec, sched, demand, cap, n_act, place)
    total = jnp.sum(demand * spec.active, -1)
    # Scheduler on: deferred work is parked backlog by design, so the
    # aggregate QoS charge counts only the *admitted* (due) demand.
    due = jnp.sum(jnp.maximum(demand - 0.8 * spec.slack(), 0.0)
                  * spec.active, -1)
    violation = jnp.where(sched[0] > 0, due, total) > cap + 1e-9

    mstate = pred_mod.observe(cfg.predictor, mstate, w_agg, predicted)
    # Availability bins are node counts: observe a count of ``a`` as bin
    # ``a − 1`` (the half-step keeps floor() off the bin edge), so the
    # forecast's upper edge maps back to exactly ``a`` usable nodes.
    astate = pred_mod.observe(
        cfg.avail_predictor, astate, (avail_t - 0.5) / cfg.n_nodes,
        pred_mod.predict(cfg.avail_predictor, astate))
    out = _StepOut(power=pwr, capacity=cap, violation=violation,
                   backlog=jnp.sum(alloc.backlog, -1),
                   predicted_bin=predicted,
                   actual_bin=actual, v_core=tables.v_core[selected],
                   v_bram=tables.v_bram[selected],
                   f_rel=tables.f_rel[selected],
                   n_active=n_act,
                   tenant_served=alloc.served,
                   tenant_backlog=alloc.backlog,
                   tenant_violation=alloc.violation,
                   tenant_starved=alloc.starved)
    return (mstate, astate, alloc.backlog, alloc.place), out


def _default_cell_tenant() -> Tuple[sched_mod.TenantSpec, Array]:
    """The aggregate-compatible tenant context: one default tenant,
    scheduler off — reproduces the legacy scalar loop bit-for-bit."""
    spec = sched_mod.TenantSpec(*[jnp.asarray(x)
                                  for x in sched_mod.default_tenants(1)])
    return spec, sched_mod.scheduler_values(sched_mod.SCHEDULERS["none"])


def _scan_control_loop(tables: BinTables, cfg: ControllerConfig,
                       trace: Array, avail: Array) -> TraceResult:
    """The §V runtime loop as one ``lax.scan`` — shared by the
    per-platform :func:`simulate` and the batched fleet path.  ``avail``
    is the per-step usable-node trace (same length as ``trace``).
    Aggregate-only: the trace rides as a single default tenant with the
    scheduler disabled (tenant planes go through the streaming path)."""
    spec, sched = _default_cell_tenant()
    init = (pred_mod.init_state(cfg.predictor),
            pred_mod.init_state(cfg.avail_predictor),
            jnp.zeros(1), jnp.zeros(1))
    (mstate, _, _, _), outs = jax.lax.scan(
        lambda c, wa: _control_step(tables, cfg, c, wa[0][None], wa[1],
                                    spec, sched),
        init, (trace, avail))
    return TraceResult(power=outs.power, capacity=outs.capacity,
                       violations=outs.violation, backlog=outs.backlog,
                       predicted_bin=outs.predicted_bin,
                       actual_bin=outs.actual_bin, v_core=outs.v_core,
                       v_bram=outs.v_bram, f_rel=outs.f_rel,
                       n_active=outs.n_active,
                       mispredictions=mstate.mispredictions,
                       margin_misses=mstate.margin_misses,
                       final_predictor=mstate)


def simulate(platform: PlatformSpec, cfg: ControllerConfig,
             trace: np.ndarray | Array,
             avail: Optional[np.ndarray | Array] = None) -> TraceResult:
    """Run the §V control loop over a workload trace (one jitted scan).

    ``avail`` is an optional per-step usable-node trace (same length as
    ``trace``); ``None`` means a healthy fleet — every step has the
    configured ``cfg.n_nodes`` available.
    """
    tables = build_bin_tables(platform, cfg)
    trace = jnp.asarray(trace, jnp.float32)
    avail = (jnp.full(trace.shape, float(cfg.n_nodes)) if avail is None
             else jnp.asarray(avail, jnp.float32))
    return _scan_control_loop(tables, cfg, trace, avail)


def summarize(platform: PlatformSpec, cfg: ControllerConfig,
              trace: np.ndarray | Array, result: TraceResult,
              avail: Optional[np.ndarray | Array] = None) -> Summary:
    """Reduce a :class:`TraceResult` to the paper's Summary metrics.

    ``avail`` is the usable-node trace the run was simulated with (when
    any).  The headline ``power_gain`` is computed against the
    *available* fleet's nominal watts — dead nodes draw nothing, so they
    earn no baseline credit; ``power_gain_vs_configured`` keeps the
    configured-``n_nodes`` comparison.  Both coincide on healthy runs.
    """
    node_nom = nominal_node_watts(platform) + pll_standing_watts(cfg)
    nominal_cfg_w = node_nom * cfg.n_nodes
    mean_avail = (float(cfg.n_nodes) if avail is None
                  else float(np.mean(np.asarray(avail))))
    nominal_w = node_nom * mean_avail
    mean_w = float(jnp.mean(result.power))
    offered = float(jnp.sum(jnp.asarray(trace)))
    served = offered - float(result.backlog[-1])
    n = result.power.shape[0]
    n_scored = max(n - cfg.predictor.warmup_steps, 1)
    return Summary(
        technique=cfg.technique,
        mean_power_w=mean_w,
        nominal_power_w=nominal_w,
        power_gain=nominal_w / mean_w,
        qos_violation_rate=float(jnp.mean(result.violations)),
        served_fraction=served / max(offered, 1e-9),
        misprediction_rate=float(result.mispredictions) / n_scored,
        mean_backlog=float(jnp.mean(result.backlog)),
        margin_misprediction_rate=float(result.margin_misses) / n_scored,
        nominal_power_configured_w=nominal_cfg_w,
        power_gain_vs_configured=nominal_cfg_w / mean_w,
    )


def run_technique(platform: PlatformSpec, trace, technique: str,
                  avail=None, **cfg_kwargs) -> Summary:
    cfg = ControllerConfig(technique=technique, **cfg_kwargs)
    result = simulate(platform, cfg, trace, avail=avail)
    return summarize(platform, cfg, trace, result, avail=avail)


def compare_all(platform: PlatformSpec, trace,
                techniques=("proposed", "core_only", "bram_only",
                            "freq_only", "power_gating", "hybrid"),
                **cfg_kwargs) -> Dict[str, Summary]:
    return {t: run_technique(platform, trace, t, **cfg_kwargs)
            for t in techniques}


# ---------------------------------------------------------------------------
# Fused fleet evaluation (one compiled program for platforms × techniques)
# ---------------------------------------------------------------------------
#
# ``compare_all`` above re-closes over ``delay_fn``/``power_fn`` per
# platform, so every (platform × technique) sweep cell traces its own XLA
# program.  The fleet path instead stacks array-parameterized
# ``PlatformParams`` along a leading axis, expresses techniques as boolean
# grid masks, and runs *one* jitted program per stage:
#
#   * ``fleet_bin_tables``  — one vmapped grid sweep builds every
#     (platform × technique) operating table;
#   * ``simulate_fleet``    — one vmapped ``lax.scan`` runs every
#     (platform × technique × trace) runtime loop.
#
# Both jits are keyed only on array *shapes* and the static
# ``ControllerConfig``, so adding a platform of the same shape never
# retraces — ``fleet_trace_counts`` exposes the trace counters for tests.

DEFAULT_TECHNIQUES = ("proposed", "core_only", "bram_only", "freq_only",
                      "power_gating", "hybrid")

_TRACE_COUNTS = {"tables": 0, "simulate": 0, "stream": 0}


def _runtime_cfg(cfg: ControllerConfig) -> ControllerConfig:
    """Normalize the static jit key for the shared runtime programs.

    The technique only changed the *tables*, the scheduler rides as
    values, and headroom's build-time fraction lives in the traced
    ``BinTables.headroom`` — none may fragment the jit cache.  The
    predictor configs stay: families compile per-kind by design.  Used
    by :func:`simulate_fleet`, :func:`simulate_fleet_stream`, and the
    AOT warmers (``core.aot``), which must agree byte-for-byte.
    """
    return dataclasses.replace(cfg, technique="proposed", scheduler="none",
                               headroom_frac=0.0)


def fleet_trace_counts() -> Dict[str, int]:
    """Process-lifetime (re)trace counters for the three fleet programs.

    Returns ``{"tables", "simulate", "stream"}`` — how many times the
    grid-sweep program (:func:`fleet_bin_tables`), the materializing scan
    (:func:`simulate_fleet`), and the streaming chunk program
    (:func:`simulate_fleet_stream`) have been traced by XLA.  The
    **zero-retrace contract**: these programs are jit-keyed only on array
    *shapes* plus the static ``ControllerConfig`` (normalized to be
    technique-independent), never on platform constants or trace
    contents.  Sweeping new accelerators, new seeds, new scenarios, or
    *replayed* instead of synthetic traces must leave the counters
    unchanged as long as the fleet shape ``[K]``, chunk size ``C``, and
    config stay the same — tests and benchmarks snapshot this dict
    before/after a sweep to catch accidental retraces (e.g.
    ``tests/test_fleet.py::test_simulate_fleet_zero_retrace``).
    """
    return dict(_TRACE_COUNTS)


@jax.jit
def _fleet_dvfs_tables_jit(params: char.PlatformParams, masks: Array,
                           levels: Array, core_grid: Array,
                           bram_grid: Array) -> volt_mod.OperatingPoint:
    """Grid-optimize every platform × sweep-row × bin in one program.

    ``params`` leaves are stacked [P, ...]; ``masks`` is [R, C, B] and
    ``levels`` is [R, M] — a row per DVFS technique *plus* one per hybrid
    node-count gear (the node axis rides the same masked sweep); returns
    an :class:`~repro.core.voltage.OperatingPoint` with [P, R, M] fields.

    The sweep body is the fused ``kernels.grid_argmin`` op: the Pallas
    kernel when lowered for a TPU, its lax reference on any other
    platform (both match the closure optimizer to ≤ 1e-5 —
    ``tests/test_kernels_grid_argmin.py``).
    """
    _TRACE_COUNTS["tables"] += 1  # Python side effect → counts tracings only
    return grid_argmin_op(params, masks, levels, core_grid, bram_grid)


@jax.jit
def _fleet_nominal_watts_jit(params: char.PlatformParams) -> Array:
    return jax.vmap(lambda p: char.params_power_watts(
        p, jnp.asarray(char.V_CORE_NOM), jnp.asarray(char.V_BRAM_NOM),
        jnp.asarray(1.0)))(params)


def _sweep_rows(cfg: ControllerConfig, techniques: Sequence[str]
                ) -> Tuple[volt_mod.VoltageGrids, Array, Array, Array]:
    """Masked sweep rows for :func:`_fleet_dvfs_tables_jit`.

    One row per DVFS technique; the hybrid/headroom node-count axis is
    expressed as extra rows (full grid mask, per-gear frequencies), so
    everything stays inside the one shape-keyed jitted program — both
    gear techniques *share* the same G rows and differ only in which
    gear the (host-side) selection step may pick.  Returns
    ``(grids, levels [M], row_masks [R, C, B], row_levels [R, M])`` —
    shared by :func:`fleet_bin_tables` and the AOT warmer
    (``core.aot.warm_fleet_programs``), so ahead-of-time compiles see
    byte-identical shapes to the live path.
    """
    dvfs = [t for t in techniques
            if t not in ("nominal", "power_gating", "hybrid", "headroom")]
    grids = volt_mod.VoltageGrids.default(cfg.v_step)
    levels = volt_mod.bin_frequency_levels(cfg.n_bins, cfg.margin,
                                           cfg.f_floor)
    row_masks = [volt_mod.technique_grid_mask(t, grids) for t in dvfs]
    row_levels = [levels] * len(dvfs)
    if "hybrid" in techniques or "headroom" in techniques:
        gears, f_node, _ = _hybrid_gears(cfg)
        full_mask = volt_mod.technique_grid_mask("hybrid", grids)
        row_masks += [full_mask] * gears.shape[0]
        row_levels += list(f_node)
    return grids, levels, jnp.stack(row_masks), jnp.stack(row_levels)


def fleet_bin_tables(params: char.PlatformParams, cfg: ControllerConfig,
                     techniques: Sequence[str] = DEFAULT_TECHNIQUES
                     ) -> BinTables:
    """§V synthesis-time tables for a whole fleet: fields are [P, T, M].

    ``params`` must be stacked (``stack_platform_params``) with leading
    axis P.  DVFS techniques share one masked full-grid sweep; nominal and
    power-gating are closed-form in the platform's nominal watts.

    **Zero-retrace contract.**  The underlying grid-sweep program
    (``_fleet_dvfs_tables_jit``) is jit-keyed only on the array
    *shapes* ``[P]`` / ``[R, C, B]`` derived from ``cfg`` and the
    technique list — platform constants are traced values, so sweeping
    new accelerators of the same fleet shape never retraces
    (``fleet_trace_counts()["tables"]`` is the witness).
    """
    with spans.span(spans.FLEET_TABLES):
        m = cfg.n_bins
        pll_watts = pll_standing_watts(cfg)
        stall = pll_mod.stall_fraction(cfg.pll, cfg.tau)
        n_p = params.watts_scale.shape[0]

        per_tech: Dict[str, BinTables] = {}
        dvfs = [t for t in techniques
                if t not in ("nominal", "power_gating", "hybrid", "headroom")]
        geared = [t for t in ("hybrid", "headroom") if t in techniques]
        if dvfs or geared:
            grids, levels, row_masks, row_levels = _sweep_rows(cfg, techniques)
            if geared:
                gears, f_node, gear_ok = _hybrid_gears(cfg)
            pts = _fleet_dvfs_tables_jit(params, row_masks, row_levels,
                                         grids.core, grids.bram)
            node_w = pts.power * params.watts_scale[:, None, None]  # [P, R, M]
            n_full = jnp.full((n_p, m), float(cfg.n_nodes))
            zeros = jnp.zeros((n_p, m))
            for i, t in enumerate(dvfs):
                per_tech[t] = BinTables(
                    capacity=jnp.broadcast_to(levels * (1.0 - stall), (n_p, m)),
                    power=(node_w[:, i] + pll_watts) * cfg.n_nodes,
                    v_core=pts.v_core[:, i], v_bram=pts.v_bram[:, i],
                    f_rel=jnp.broadcast_to(levels, (n_p, m)), n_active=n_full,
                    node_power=node_w[:, i] + pll_watts, gated_power=zeros,
                    headroom=jnp.zeros(n_p))
            # hybrid and headroom share the same G gear rows of the one
            # sweep; headroom's reserve is a *runtime* policy, flagged to
            # ``_headroom_bump`` by the headroom field — no extra compiled
            # work, identical operating tables.
            h_w = node_w[:, len(dvfs):]                           # [P, G, M]
            for t in geared:
                nom_w = _fleet_nominal_watts_jit(params)          # [P]
                total = (gears[None, :, None] * (h_w + pll_watts)
                         + (cfg.n_nodes - gears[None, :, None])
                         * cfg.gated_power_frac * nom_w[:, None, None])
                total = jnp.where(gear_ok[None], total, jnp.inf)
                gi = jnp.argmin(total, axis=1)                    # [P, M]

                def pick(x):  # gather the chosen gear from a [P, G, M] field
                    return jnp.take_along_axis(x, gi[:, None], axis=1)[:, 0]

                f_sel = pick(jnp.broadcast_to(f_node[None], h_w.shape))
                n_sel = gears[gi]
                per_tech[t] = BinTables(
                    capacity=(n_sel / cfg.n_nodes) * f_sel * (1.0 - stall),
                    power=pick(total),
                    v_core=pick(pts.v_core[:, len(dvfs):]),
                    v_bram=pick(pts.v_bram[:, len(dvfs):]),
                    f_rel=f_sel, n_active=n_sel,
                    node_power=pick(h_w) + pll_watts,
                    gated_power=jnp.broadcast_to(
                        (cfg.gated_power_frac * nom_w)[:, None], (n_p, m)),
                    headroom=jnp.full(n_p, cfg.headroom_frac
                                      if t == "headroom" else 0.0))

        if "nominal" in techniques or "power_gating" in techniques:
            node_w = _fleet_nominal_watts_jit(params)  # [P]
            nom_vc = jnp.full((n_p, m), char.V_CORE_NOM)
            nom_vb = jnp.full((n_p, m), char.V_BRAM_NOM)
            ones = jnp.ones((n_p, m))
            if "nominal" in techniques:
                per_tech["nominal"] = BinTables(
                    capacity=ones,
                    power=jnp.broadcast_to(
                        ((node_w + pll_watts) * cfg.n_nodes)[:, None], (n_p, m)),
                    v_core=nom_vc, v_bram=nom_vb, f_rel=ones,
                    n_active=jnp.full((n_p, m), float(cfg.n_nodes)),
                    node_power=jnp.broadcast_to((node_w + pll_watts)[:, None],
                                                (n_p, m)),
                    gated_power=jnp.zeros((n_p, m)),
                    headroom=jnp.zeros(n_p))
            if "power_gating" in techniques:
                edges = (np.arange(m) + 1.0) / m
                n_active = jnp.asarray(np.minimum(np.ceil(edges * cfg.n_nodes),
                                                  cfg.n_nodes), jnp.float32)
                gated = ((cfg.n_nodes - n_active) * cfg.gated_power_frac
                         * node_w[:, None])
                per_tech["power_gating"] = BinTables(
                    capacity=jnp.broadcast_to(n_active / cfg.n_nodes, (n_p, m)),
                    power=n_active * (node_w[:, None] + pll_watts) + gated,
                    v_core=nom_vc, v_bram=nom_vb, f_rel=ones,
                    n_active=jnp.broadcast_to(n_active, (n_p, m)),
                    node_power=jnp.broadcast_to((node_w + pll_watts)[:, None],
                                                (n_p, m)),
                    gated_power=jnp.broadcast_to(
                        (cfg.gated_power_frac * node_w)[:, None], (n_p, m)),
                    headroom=jnp.zeros(n_p))

        return BinTables(*[jnp.stack([getattr(per_tech[t], f) for t in techniques],
                                     axis=1)
                           for f in BinTables._fields])


@functools.partial(jax.jit, static_argnames=("cfg",))
def _simulate_fleet_jit(tables: BinTables, traces: Array, avail: Array,
                        cfg: ControllerConfig) -> TraceResult:
    """One vmapped ``lax.scan`` over the flattened [K] fleet axis.

    ``avail`` always rides along (all-``n_nodes`` for healthy fleets), so
    availability-bearing and healthy sweeps share one compiled program.
    """
    _TRACE_COUNTS["simulate"] += 1
    return jax.vmap(lambda tab, trace, av: _scan_control_loop(tab, cfg,
                                                              trace, av)
                    )(tables, traces, avail)


def _broadcast_traces(traces: np.ndarray, lead: Tuple[int, ...]) -> np.ndarray:
    """Expand traces to ``lead + (S,)`` as a zero-copy numpy view.

    Accepts a single shared trace [S] or per-cell traces whose leading
    axes match ``lead`` dim-for-dim (1s broadcast).  Stays in numpy with
    stride-0 broadcasting so a shared million-step trace never costs
    ``K·S`` memory — the streaming path materializes one chunk at a time.
    """
    traces = np.asarray(traces, np.float32)
    if traces.ndim == 1:
        return np.broadcast_to(traces, lead + traces.shape)
    if (traces.ndim - 1 == len(lead)
            and all(a == b or a == 1
                    for a, b in zip(traces.shape[:-1], lead))):
        return np.broadcast_to(traces, lead + traces.shape[-1:])
    # No rank-extending broadcasting: [P, S] traces against [P, T, M]
    # tables would silently line P up against T whenever P == T.
    raise ValueError(
        f"traces leading axes {traces.shape[:-1]} must match the "
        f"tables' leading axes {lead} dim-for-dim (1s broadcast), or "
        "pass a single [S] trace; expand per-platform traces to "
        "[P, 1, S] explicitly")


def _broadcast_avail(avail, lead: Tuple[int, ...], n_nodes: int,
                     s: int) -> np.ndarray:
    """Expand a usable-nodes schedule to ``lead + (S,)`` (stride-0).

    ``None`` means a healthy fleet: every step has ``n_nodes`` available
    — materialized as a zero-copy broadcast so the always-present
    availability input never costs ``K·S`` memory.
    """
    if avail is None:
        return np.broadcast_to(np.float32(n_nodes), lead + (s,))
    avail = _broadcast_traces(np.asarray(avail), lead)
    if avail.shape[-1] != s:
        raise ValueError(f"avail length {avail.shape[-1]} != trace "
                         f"length {s}")
    return avail


def simulate_fleet(tables: BinTables, traces: np.ndarray | Array,
                   cfg: ControllerConfig,
                   avail: Optional[np.ndarray | Array] = None
                   ) -> TraceResult:
    """Run the §V loop for every fleet cell in one compiled program.

    ``tables`` fields carry arbitrary leading axes ``[..., M]`` (e.g.
    [P, T, M] from :func:`fleet_bin_tables`); ``traces`` is either one
    shared trace [S] or per-cell traces broadcastable to ``[..., S]``.
    ``avail`` is an optional usable-nodes schedule with the same
    broadcasting rules ([S] shared or per-cell ``[..., S]``); ``None``
    means every step has ``cfg.n_nodes`` available.  Because the healthy
    case is an all-``n_nodes`` schedule of the same shape, adding an
    availability schedule never compiles a second program.
    Returns a :class:`TraceResult` whose fields have shape ``[..., S]``.
    The jit cache is keyed on shapes + the static config (normalized to be
    technique-independent — the runtime loop is shared across techniques),
    so repeat calls with same-shaped inputs never retrace.

    Memory scales as ``10·K·S`` floats (every per-step field is
    materialized); for long traces use :func:`simulate_fleet_stream`.
    """
    lead = tables.capacity.shape[:-1]
    k = int(np.prod(lead, dtype=np.int64)) if lead else 1
    flat = BinTables(*[jnp.reshape(x, (k,) + x.shape[len(lead):])
                       for x in tables])
    traces = _broadcast_traces(np.asarray(traces), lead)
    s = traces.shape[-1]
    avail = _broadcast_avail(avail, lead, cfg.n_nodes, s)
    traces = jnp.asarray(np.ascontiguousarray(traces)).reshape((k, s))
    avail = jnp.asarray(np.ascontiguousarray(avail)).reshape((k, s))
    # Normalize the static jit key: the technique only changed the
    # tables, and this aggregate path never acts on the scheduler.
    cfg = _runtime_cfg(cfg)
    out = _simulate_fleet_jit(flat, traces, avail, cfg)
    return jax.tree_util.tree_map(
        lambda x: jnp.reshape(x, lead + x.shape[1:]), out)


# ---------------------------------------------------------------------------
# Streaming fleet evaluation (trace-length-independent compile, O(K) memory)
# ---------------------------------------------------------------------------
#
# ``_simulate_fleet_jit`` materializes all ten per-step TraceResult fields
# as [K, S] arrays — memory is 10·K·S floats and the compiled program is
# keyed on S, so million-step traces are impossible and every new trace
# length retraces.  The streaming path instead accumulates the Summary
# reductions (power/violation/backlog sums, offered work, final predictor
# state) *inside* the scan carry and consumes the trace in fixed-size
# [K, C] chunks: one jitted chunk program keyed only on (K, C), driven by
# a host loop.  Per-step fields are only materialized on request (`emit`).
# The flattened fleet axis K is sharded across local devices through the
# ``parallel.sharding`` helpers — each cell is independent, so the chunk
# program partitions along K with zero cross-device communication.


class _StreamAcc(NamedTuple):
    """Streaming scan carry: controller state + in-carry reductions.

    ``backlog``/``place`` are per-tenant ``[T]`` carries; the ``t_*``
    fields are per-tenant reduction sums ``[T]`` (aggregate callers ride
    them with ``T = 1``)."""

    mstate: pred_mod.PredictorState
    astate: pred_mod.PredictorState   # availability-plane forecaster
    backlog: Array       # [T] carried per-tenant backlog
    place: Array         # [T] per-tenant node placement (bin-packing state)
    power_sum: Array     # Σ watts over valid steps
    viol_sum: Array      # Σ violations
    backlog_sum: Array   # Σ aggregate backlog (the backlog integral)
    offered_sum: Array   # Σ aggregate w_t
    avail_sum: Array     # Σ usable nodes (the availability integral)
    t_viol_sum: Array    # [T] Σ per-tenant QoS violations
    t_starve_sum: Array  # [T] Σ per-tenant starvation steps
    t_served_sum: Array  # [T] Σ per-tenant served work
    t_offered_sum: Array  # [T] Σ per-tenant offered work


class FleetSummary(NamedTuple):
    """Per-cell reductions from a streaming fleet run.

    Every field carries the tables' leading axes (e.g. ``[P, T]`` or
    ``[P, T, N]``) — never the trace length.  ``emitted`` holds the
    explicitly requested per-step fields (``[..., S]`` host arrays).
    """

    mean_power_w: np.ndarray
    qos_violation_rate: np.ndarray
    served_fraction: np.ndarray
    mean_backlog: np.ndarray
    final_backlog: np.ndarray
    offered: np.ndarray
    mispredictions: np.ndarray
    n_steps: int
    final_predictor: pred_mod.PredictorState
    emitted: Dict[str, np.ndarray]
    #: Mean usable nodes per step — ``cfg.n_nodes`` on healthy runs; the
    #: available-fleet nominal baseline is ``mean_avail_nodes`` × the
    #: per-node nominal watts.
    mean_avail_nodes: np.ndarray = None
    #: Post-warmup beyond-margin misses per cell (see
    #: ``Summary.margin_misprediction_rate``).
    margin_misses: np.ndarray = None
    #: Per-tenant QoS accounting ``[..., T]`` (T = 1 for aggregate
    #: runs): rate of steps whose carried backlog exceeded the tenant's
    #: latency slack / rate of steps the tenant had demand but received
    #: no service / served-over-offered work fraction / final carried
    #: backlog.  Padding tenants report zeros.
    tenant_qos_violation_rate: np.ndarray = None
    tenant_starvation_rate: np.ndarray = None
    tenant_served_fraction: np.ndarray = None
    tenant_final_backlog: np.ndarray = None


@functools.partial(jax.jit, static_argnames=("cfg", "emit"))
def _fleet_stream_chunk_jit(tables: BinTables,
                            mstate: pred_mod.PredictorState,
                            astate: pred_mod.PredictorState,
                            backlog: Array, place: Array, chunk: Array,
                            avail: Array, valid: Array,
                            spec: sched_mod.TenantSpec, sched: Array,
                            cfg: ControllerConfig,
                            emit: Tuple[str, ...]) -> Tuple:
    """One fixed-shape streaming chunk over the flattened [K] fleet axis.

    ``chunk`` is the tenant-resolved workload plane [K, C, T] and
    ``avail`` is [K, C] (the tail chunk zero-padded) — availability
    always rides the chunk program (all-``n_nodes`` for healthy
    fleets), so failure-bearing sweeps share the compiled program;
    ``backlog``/``place`` are the [K, T] per-tenant carries and
    ``spec`` the per-cell tenant classes ([K, T] leaves).  The
    scheduler vector ``sched`` and every ``spec`` leaf are traced
    *values*: scheduler-on/off sweeps, priority/latency sweeps, and
    tenant-count sweeps (at a padded width) all reuse this one
    program — aggregate callers ride it with T = 1.  ``valid`` is a
    [C] mask; invalid steps pass the carry through unchanged, so
    partial tail chunks reuse the same compiled program.  Reduction
    sums restart at zero each chunk — the host accumulates them in
    float64, keeping long-trace sums out of float32 range.
    """
    _TRACE_COUNTS["stream"] += 1

    def cell(tab, ms, ast, bl, pl, tr, av, sp):
        zero = jnp.asarray(0.0, jnp.float32)
        zt = jnp.zeros_like(bl)
        acc0 = _StreamAcc(mstate=ms, astate=ast, backlog=bl, place=pl,
                          power_sum=zero,
                          viol_sum=zero, backlog_sum=zero, offered_sum=zero,
                          avail_sum=zero, t_viol_sum=zt, t_starve_sum=zt,
                          t_served_sum=zt, t_offered_sum=zt)

        def step(a, inp):
            w_t, a_t, v = inp
            (ms2, ast2, bl2, pl2), out = _control_step(
                tab, cfg, (a.mstate, a.astate, a.backlog, a.place), w_t,
                a_t, sp, sched)
            new = _StreamAcc(
                mstate=ms2, astate=ast2, backlog=bl2, place=pl2,
                power_sum=a.power_sum + out.power,
                viol_sum=a.viol_sum + out.violation.astype(jnp.float32),
                backlog_sum=a.backlog_sum + out.backlog,
                offered_sum=a.offered_sum + jnp.sum(w_t * sp.active, -1),
                avail_sum=a.avail_sum + a_t,
                t_viol_sum=(a.t_viol_sum
                            + out.tenant_violation.astype(jnp.float32)),
                t_starve_sum=(a.t_starve_sum
                              + out.tenant_starved.astype(jnp.float32)),
                t_served_sum=a.t_served_sum + out.tenant_served,
                t_offered_sum=a.t_offered_sum + w_t * sp.active)
            a2 = jax.tree.map(lambda n, o: jnp.where(v, n, o), new, a)
            return a2, tuple(getattr(out, e) for e in emit)

        return jax.lax.scan(step, acc0, (tr, av, valid))

    return jax.vmap(cell, in_axes=(0, 0, 0, 0, 0, 0, 0, 0))(
        tables, mstate, astate, backlog, place, chunk, avail, spec)


def _broadcast_tenant_traces(traces: np.ndarray, lead: Tuple[int, ...],
                             n_tenants: int) -> np.ndarray:
    """Expand a tenant plane to ``lead + (S, T)`` as a zero-copy view.

    Accepts a single shared plane [S, T] or per-cell planes whose
    leading axes match ``lead`` dim-for-dim (1s broadcast) — the tenant
    variant of :func:`_broadcast_traces`, with the same
    no-rank-extension rule for the leading axes.
    """
    traces = np.asarray(traces, np.float32)
    if traces.ndim < 2 or traces.shape[-1] != n_tenants:
        raise ValueError(
            f"tenant plane must end in [S, T={n_tenants}] to match the "
            f"tenant spec, got shape {traces.shape}")
    if traces.ndim == 2:
        return np.broadcast_to(traces, lead + traces.shape)
    if (traces.ndim - 2 == len(lead)
            and all(a == b or a == 1
                    for a, b in zip(traces.shape[:-2], lead))):
        return np.broadcast_to(traces, lead + traces.shape[-2:])
    raise ValueError(
        f"tenant plane leading axes {traces.shape[:-2]} must match the "
        f"tables' leading axes {lead} dim-for-dim (1s broadcast), or "
        "pass a single shared [S, T] plane")


def _flatten_tenant_spec(spec: sched_mod.TenantSpec, lead: Tuple[int, ...],
                         k: int, k_pad: int) -> sched_mod.TenantSpec:
    """Broadcast spec leaves to ``lead + (T,)`` and flatten to [k_pad, T].

    Accepts shared [T] leaves or per-cell ``lead + (T,)`` leaves (1s
    broadcast); fleet-axis padding replays cell 0, matching the trace
    rows.
    """
    t = spec.n_tenants

    def one(x, name):
        x = np.asarray(x, np.float32)
        if x.ndim == 0 or x.shape[-1] != t:
            raise ValueError(f"tenant spec leaf {name!r} must end in "
                             f"[T={t}], got shape {x.shape}")
        if x.ndim == 1:
            x = np.broadcast_to(x, lead + x.shape)
        elif (x.ndim - 1 == len(lead)
                and all(a == b or a == 1
                        for a, b in zip(x.shape[:-1], lead))):
            x = np.broadcast_to(x, lead + x.shape[-1:])
        else:
            raise ValueError(
                f"tenant spec leaf {name!r} leading axes {x.shape[:-1]} "
                f"must match the tables' leading axes {lead} dim-for-dim "
                "(1s broadcast), or pass shared [T] leaves")
        flat = np.ascontiguousarray(x).reshape(k, t)
        if k_pad != k:
            flat = np.concatenate(
                [flat, np.broadcast_to(flat[:1], (k_pad - k, t))])
        return jnp.asarray(flat)

    return sched_mod.TenantSpec(*[one(x, n) for n, x in
                                  zip(spec._fields, spec)])


def simulate_fleet_stream(tables: BinTables, traces: np.ndarray | Array,
                          cfg: ControllerConfig, chunk_size: int = 1024,
                          emit: Sequence[str] = (),
                          shard: bool = True,
                          avail: Optional[np.ndarray | Array] = None,
                          tenant_spec: Optional[sched_mod.TenantSpec] = None
                          ) -> FleetSummary:
    """Streaming :func:`simulate_fleet`: O(K) memory, any trace length.

    **Shape conventions.**  ``tables`` fields carry arbitrary leading
    axes ``[..., M]`` (e.g. ``[P, T, M]`` from :func:`fleet_bin_tables`,
    or ``[P, T, N, M]`` with a scenario axis); those leading axes flatten
    into one fleet axis ``K`` — every (platform × technique × trace)
    cell is an independent §V control loop.  ``traces`` is one shared
    trace ``[S]`` or per-cell traces broadcastable to ``[..., S]``
    (stride-0 numpy broadcasting: a shared million-step trace never
    materializes ``K·S`` floats).  The device program, however, never
    sees ``[K, S]``: the host loop feeds fixed ``[K, C]`` chunks
    (``C = chunk_size``; the tail chunk is zero-padded under a validity
    mask), so compiled shapes — and therefore the jit cache key — are
    ``(K, C)`` + the static config, *independent of S*.  Replayed,
    synthetic, short, and million-step traces of the same fleet shape
    all reuse one cache entry (the zero-retrace contract;
    :func:`fleet_trace_counts`\\ ``()["stream"]`` is the witness).

    **Availability.**  ``avail`` is an optional per-step usable-nodes
    schedule with the same broadcasting rules as ``traces`` ([S] shared
    or per-cell ``[..., S]``); it rides the same ``[K, C]`` chunks as
    the workload.  ``None`` means a healthy fleet — a stride-0
    all-``n_nodes`` schedule is fed instead, so the chunk program always
    has the availability input and adding a failure schedule never
    compiles a second program.

    **Reductions and ``emit=``.**  The ``Summary`` reductions
    (power/violation/backlog sums, offered work, predictor state) ride
    the scan carry; per-chunk partial sums are accumulated on the host in
    float64, so long-trace sums stay out of float32 range.  By default no
    per-step field is materialized; ``emit`` names :class:`TraceResult`
    per-step fields (e.g. ``emit=("power", "f_rel")``) to collect as
    ``[..., S]`` host arrays in ``FleetSummary.emitted`` — opting back
    into O(S) memory for exactly the requested fields.  Changing ``emit``
    changes the compiled program (it is a static jit argument).

    **Sharding.**  With more than one local device and ``shard=True`` the
    flattened fleet axis ``K`` is sharded across devices via the
    ``parallel.sharding`` fleet helpers (cells are independent, so the
    chunk program partitions with no collectives); ``K`` is padded up to
    a device-count multiple with replayed rows that are dropped from
    every result.

    **Tenants.**  ``tenant_spec`` (a
    :class:`~repro.core.scheduler.TenantSpec` with shared ``[T]`` or
    per-cell ``lead + (T,)`` leaves) switches ``traces`` to a
    tenant-resolved plane — shared ``[S, T]`` or per-cell
    ``[..., S, T]`` — whose device chunks are ``[K, C, T]``.  The
    scheduler selected by ``cfg.scheduler`` then splits every step's
    delivered capacity across tenants *inside* the chunk scan (and
    shapes the provisioned bin — the DVFS co-optimization); per-tenant
    QoS lands in the ``tenant_*`` FleetSummary fields.  Without a spec
    the workload rides as one default tenant with the scheduler off —
    bit-for-bit the legacy aggregate loop, through the same chunk
    program at ``T = 1``.  Spec leaves and the scheduler knobs are
    traced values, so scheduler-on/off and tenant-class sweeps never
    retrace; tenant-*count* sweeps reuse the program at any common
    padded width (:func:`~repro.core.scheduler.pad_tenants`).

    Matches the materialized path to float32 reduction accuracy (≤1e-5
    relative — see tests/test_fleet.py).
    """
    with spans.span(spans.STREAM_SETUP):
        # emit accepts TraceResult per-step names; internally _StepOut names
        # one field differently ("violations" → "violation").
        alias = {"violations": "violation"}
        emit = tuple(emit)
        emit_internal = tuple(alias.get(e, e) for e in emit)
        for e, ei in zip(emit, emit_internal):
            if ei not in _EMITTABLE:
                per_step = tuple(f for f in TraceResult._fields
                                 if f not in ("mispredictions",
                                              "final_predictor"))
                raise ValueError(f"unknown emit field {e!r}; "
                                 f"choose from {per_step}")
        lead = tables.capacity.shape[:-1]
        k = int(np.prod(lead, dtype=np.int64)) if lead else 1
        flat = BinTables(*[jnp.reshape(x, (k,) + x.shape[len(lead):])
                           for x in tables])
        # Keep traces/availability in their lead + (S, …) stride-0 broadcast
        # form — a dense (K, S) reshape here would silently copy K·S floats
        # (numpy cannot express it as a view), breaking the O(K) memory
        # contract.  Only the per-chunk slices below ever materialize.
        spec_in = tenant_spec if tenant_spec is not None \
            else sched_mod.default_tenants(1)
        t = spec_in.n_tenants
        if tenant_spec is None:
            # Aggregate workload: ride the tenant plane as a single default
            # tenant — the trailing axis is a stride-0 numpy view.
            traces = _broadcast_traces(np.asarray(traces), lead)[..., None]
        else:
            traces = _broadcast_tenant_traces(np.asarray(traces), lead, t)
        s = traces.shape[-2]
        avail_full = _broadcast_avail(avail, lead, cfg.n_nodes, s)
        c = max(1, min(int(chunk_size), s))
        scfg = cfg.scheduler if tenant_spec is not None \
            else sched_mod.SCHEDULERS["none"]
        sched_vals = sched_mod.scheduler_values(scfg)
        # Normalize the static jit key: the technique only changed the
        # tables, and the scheduler rides as values.
        cfg = _runtime_cfg(cfg)

        mesh = shd.fleet_mesh() if shard else None
        k_pad = k
        if mesh is not None:
            d = mesh.devices.size
            k_pad = -(-k // d) * d
        if k_pad != k:
            # Pad the fleet axis so it divides the device count; padded cells
            # replay cell 0 and are dropped from every result below.  The
            # trace rows are padded per *chunk* (below), never as a dense
            # [k_pad, S] array — the O(K·C) memory contract must survive
            # sharding.
            pad = [(0, k_pad - k)] + [(0, 0)] * (flat.capacity.ndim - 1)
            flat = BinTables(*[jnp.pad(x, pad[:x.ndim], mode="edge")
                               for x in flat])

        spec = _flatten_tenant_spec(spec_in, lead, k, k_pad)
        mstate = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (k_pad,) + x.shape),
            pred_mod.init_state(cfg.predictor))
        astate = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (k_pad,) + x.shape),
            pred_mod.init_state(cfg.avail_predictor))
        backlog = jnp.zeros((k_pad, t), jnp.float32)
        place = jnp.zeros((k_pad, t), jnp.float32)
        if mesh is not None:
            rules = shd.fleet_rules(mesh)
            flat = shd.shard_fleet(flat, rules)
            mstate = shd.shard_fleet(mstate, rules)
            astate = shd.shard_fleet(astate, rules)
            backlog = shd.shard_fleet(backlog, rules)
            place = shd.shard_fleet(place, rules)
            spec = shd.shard_fleet(spec, rules)

        power_sum = np.zeros(k_pad, np.float64)
        viol_sum = np.zeros(k_pad, np.float64)
        backlog_sum = np.zeros(k_pad, np.float64)
        offered_sum = np.zeros(k_pad, np.float64)
        avail_sum = np.zeros(k_pad, np.float64)
        t_viol_sum = np.zeros((k_pad, t), np.float64)
        t_starve_sum = np.zeros((k_pad, t), np.float64)
        t_served_sum = np.zeros((k_pad, t), np.float64)
        t_offered_sum = np.zeros((k_pad, t), np.float64)

        def chunked(rows, s0, n_valid):
            """One [k_pad, C] device chunk of a lead + (S,) row set.

            ``rows`` may be a stride-0 broadcast; slicing the step axis keeps
            the view, so only k·C elements materialize per chunk — never K·S.
            """
            raw = np.ascontiguousarray(rows[..., s0:s0 + c]).reshape((k, -1))
            if n_valid < c:
                raw = np.pad(raw, ((0, 0), (0, c - n_valid)))
            if k_pad != k:
                raw = np.concatenate(
                    [raw, np.broadcast_to(raw[:1], (k_pad - k, raw.shape[-1]))])
            out = jnp.asarray(raw)
            return shd.shard_fleet(out, rules) if mesh is not None else out

        def chunked_plane(rows, s0, n_valid):
            """One [k_pad, C, T] device chunk of the lead + (S, T) plane."""
            raw = np.ascontiguousarray(
                rows[..., s0:s0 + c, :]).reshape((k, -1, t))
            if n_valid < c:
                raw = np.pad(raw, ((0, 0), (0, c - n_valid), (0, 0)))
            if k_pad != k:
                raw = np.concatenate(
                    [raw, np.broadcast_to(raw[:1],
                                          (k_pad - k,) + raw.shape[1:])])
            out = jnp.asarray(raw)
            return shd.shard_fleet(out, rules) if mesh is not None else out

        # Healthy fleets have a constant all-n_nodes schedule: build its
        # device chunk once and reuse it, instead of re-materializing and
        # re-transferring an identical [k_pad, C] array every chunk.
        # (Padded/invalid steps never escape — the valid mask gates the
        # carry and emits are cut to n_valid — so one chunk fits all.)
        av_const = None
        if avail is None:
            av_const = jnp.full((k_pad, c), jnp.float32(cfg.n_nodes))
            if mesh is not None:
                av_const = shd.shard_fleet(av_const, rules)

        emitted = {e: [] for e in emit}

    for s0 in range(0, s, c):
        n_valid = min(c, s - s0)
        with spans.span(spans.STREAM_STAGE):
            chunk = chunked_plane(traces, s0, n_valid)
            av_chunk = (av_const if av_const is not None
                        else chunked(avail_full, s0, n_valid))
            valid = jnp.asarray(np.arange(c) < n_valid)
        with spans.span(spans.STREAM_DISPATCH):
            acc, ys = _fleet_stream_chunk_jit(flat, mstate, astate, backlog,
                                              place, chunk, av_chunk, valid,
                                              spec, sched_vals, cfg,
                                              emit_internal)
        with spans.span(spans.STREAM_PULL):
            mstate, astate = acc.mstate, acc.astate
            backlog, place = acc.backlog, acc.place
            power_sum += np.asarray(acc.power_sum, np.float64)
            viol_sum += np.asarray(acc.viol_sum, np.float64)
            backlog_sum += np.asarray(acc.backlog_sum, np.float64)
            offered_sum += np.asarray(acc.offered_sum, np.float64)
            avail_sum += np.asarray(acc.avail_sum, np.float64)
            t_viol_sum += np.asarray(acc.t_viol_sum, np.float64)
            t_starve_sum += np.asarray(acc.t_starve_sum, np.float64)
            t_served_sum += np.asarray(acc.t_served_sum, np.float64)
            t_offered_sum += np.asarray(acc.t_offered_sum, np.float64)
            for e, y in zip(emit, ys):
                emitted[e].append(np.asarray(y[:, :n_valid]))

    with spans.span(spans.STREAM_FINISH):
        def cut(x):
            x = np.asarray(x)[:k]
            return x.reshape(lead + x.shape[1:])

        backlog_np = np.asarray(backlog, np.float64)
        served = offered_sum - backlog_np.sum(-1)
        return FleetSummary(
            mean_power_w=cut(power_sum / s),
            qos_violation_rate=cut(viol_sum / s),
            served_fraction=cut(served / np.maximum(offered_sum, 1e-9)),
            mean_backlog=cut(backlog_sum / s),
            final_backlog=cut(backlog_np.sum(-1)),
            offered=cut(offered_sum),
            mispredictions=cut(mstate.mispredictions),
            n_steps=s,
            final_predictor=jax.tree.map(cut, mstate),
            emitted={e: cut(np.concatenate(v, axis=-1))
                     for e, v in emitted.items()},
            mean_avail_nodes=cut(avail_sum / s),
            margin_misses=cut(mstate.margin_misses),
            tenant_qos_violation_rate=cut(t_viol_sum / s),
            tenant_starvation_rate=cut(t_starve_sum / s),
            tenant_served_fraction=cut(t_served_sum
                                       / np.maximum(t_offered_sum, 1e-9)),
            tenant_final_backlog=cut(backlog_np))


def fleet_node_nominal_watts(params: char.PlatformParams,
                             cfg: ControllerConfig) -> np.ndarray:
    """Per-platform nominal watts of ONE node (incl. PLLs) [P].

    Multiply by a node count to price a fleet baseline: ``cfg.n_nodes``
    for the configured fleet, a mean usable-node count for the
    availability-aware baseline.
    """
    return (np.asarray(_fleet_nominal_watts_jit(params))
            + pll_standing_watts(cfg))


def fleet_nominal_watts(params: char.PlatformParams,
                        cfg: ControllerConfig) -> np.ndarray:
    """Per-platform *configured*-fleet nominal watts [P] — the
    ``power_gain_vs_configured`` denominator (and ``power_gain``'s on
    healthy fleets)."""
    return fleet_node_nominal_watts(params, cfg) * cfg.n_nodes


def compare_all_batched(platforms: Sequence[PlatformSpec],
                        trace: np.ndarray | Array,
                        techniques: Sequence[str] = DEFAULT_TECHNIQUES,
                        **cfg_kwargs) -> Dict[str, Dict[str, Summary]]:
    """Batched ``compare_all`` over many platforms: one fused program.

    Returns ``{platform.name: {technique: Summary}}`` matching the
    per-platform ``compare_all`` summaries (same math, array-parameterized).
    Every platform needs ``params`` (all factory helpers attach them).

    **Zero-retrace contract.**  Both stages run shape-keyed compiled
    programs (:func:`fleet_bin_tables` + :func:`simulate_fleet`): the
    jit key is the fleet shape ``[P, T]``, the trace length, and the
    static config — new platforms and new trace *values* of the same
    shapes reuse the compiled programs without retracing
    (``tests/test_fleet.py::test_simulate_fleet_zero_retrace``).
    """
    missing = [p.name for p in platforms if p.params is None]
    if missing:
        raise ValueError(f"platforms lack PlatformParams: {missing}")
    names = [p.name for p in platforms]
    if len(set(names)) != len(names):
        dupes = sorted({n for n in names if names.count(n) > 1})
        raise ValueError(f"duplicate platform names {dupes}: results are "
                         "keyed by name — pass distinct names (e.g. "
                         "tpu_platform(..., name=...))")
    cfg = ControllerConfig(**cfg_kwargs)
    params = char.stack_platform_params([p.params for p in platforms])
    tables = fleet_bin_tables(params, cfg, techniques)     # [P, T, M]
    res = simulate_fleet(tables, trace, cfg)               # [P, T, S]

    nominal_w = fleet_nominal_watts(params, cfg)           # [P]
    offered = float(jnp.sum(jnp.asarray(trace, jnp.float32)))
    power = np.asarray(res.power)
    viol = np.asarray(res.violations)
    backlog = np.asarray(res.backlog)
    mispred = np.asarray(res.mispredictions)
    margin_miss = np.asarray(res.margin_misses)
    n_scored = max(power.shape[-1] - cfg.predictor.warmup_steps, 1)

    out: Dict[str, Dict[str, Summary]] = {}
    for i, plat in enumerate(platforms):
        per_tech = {}
        for j, tech in enumerate(techniques):
            mean_w = float(power[i, j].mean())
            served = offered - float(backlog[i, j, -1])
            per_tech[tech] = Summary(
                technique=tech,
                mean_power_w=mean_w,
                nominal_power_w=float(nominal_w[i]),
                power_gain=float(nominal_w[i]) / mean_w,
                qos_violation_rate=float(viol[i, j].mean()),
                served_fraction=served / max(offered, 1e-9),
                misprediction_rate=float(mispred[i, j]) / n_scored,
                mean_backlog=float(backlog[i, j].mean()),
                margin_misprediction_rate=float(margin_miss[i, j]) / n_scored,
                nominal_power_configured_w=float(nominal_w[i]),
                power_gain_vs_configured=float(nominal_w[i]) / mean_w,
            )
        out[plat.name] = per_tech
    return out
