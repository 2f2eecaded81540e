"""Predictor-layer tests (paper §IV-A, §V): registry, families, scoring."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests need hypothesis (pip install -r requirements-dev.txt)
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs it
    HAVE_HYPOTHESIS = False

from repro.core import predictors as pred


def _bin_w(b, n_bins):
    """A workload fraction landing exactly in bin ``b``."""
    return (b + 0.5) / n_bins


def _run(cfg, trace):
    state = pred.init_state(cfg)
    preds = []
    for w in trace:
        p = pred.predict(cfg, state)
        state = pred.observe(cfg, state, jnp.asarray(w), p)
        preds.append(int(p))
    return state, np.asarray(preds)


# ---------------------------------------------------------------------------
# Registry + config validation
# ---------------------------------------------------------------------------


def test_registry_has_all_families():
    assert set(pred.available()) >= {"markov", "persistence", "ewma",
                                     "holt_winters", "hierarchy"}
    for kind in pred.available():
        assert pred.get(kind).name == kind


def test_unknown_kind_raises_eagerly_and_in_get():
    with pytest.raises(ValueError, match="unknown predictor kind"):
        pred.PredictorConfig(kind="nope")
    with pytest.raises(KeyError, match="unknown predictor kind"):
        pred.get("nope")


@pytest.mark.parametrize("bad", [
    dict(policy="zzz"), dict(update_mode="zzz"),
    dict(quantile=0.0), dict(quantile=1.5),
    dict(count_decay=0.0), dict(count_decay=1.1),
    dict(warmup_steps=-1), dict(n_bins=0), dict(margin_bins=-1),
    dict(ewma_alpha=0.0), dict(hw_alpha=2.0), dict(hw_beta=0.0),
    dict(hw_gamma=-0.1), dict(season=-1),
    dict(hier_scales=()), dict(hier_scales=(4, 1)), dict(hier_scales=(0,)),
    dict(hurst=0.3), dict(hurst=1.2),
])
def test_config_validation_is_eager(bad):
    """Bad knobs fail at construction with one-line errors — never as
    trace-time failures inside jitted code."""
    with pytest.raises(ValueError):
        pred.PredictorConfig(**bad)


def test_state_spec_matches_init_state():
    """The AOT abstract shapes must be byte-identical to the live state
    (shape-stable carries are the zero-retrace foundation)."""
    for kind in pred.available():
        cfg = pred.PredictorConfig(kind=kind, n_bins=7, season=5)
        spec = pred.state_spec(cfg)
        live = pred.init_state(cfg)
        jax.tree.map(
            lambda s, x: (s.shape, s.dtype) == (x.shape, x.dtype)
            or pytest.fail(f"{kind}: spec {s} != live {x.shape}"),
            spec, live)


# ---------------------------------------------------------------------------
# Shared shell: warmup, exact + margin-aware scoring
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", sorted(pred.available()))
def test_warmup_predicts_nominal(kind):
    """§IV-A: the first I steps run at maximum frequency — every family."""
    cfg = pred.PredictorConfig(kind=kind, n_bins=8, warmup_steps=10)
    state = pred.init_state(cfg)
    for _ in range(10):
        p = pred.predict(cfg, state)
        assert int(p) == cfg.n_bins - 1
        state = pred.observe(cfg, state, jnp.asarray(_bin_w(2, 8)), p)
    assert int(state.mispredictions) == 0  # warmup is never scored


def test_margin_scoring_charges_only_beyond_margin_underpredictions():
    """margin_misses counts exactly ``actual > predicted + margin_bins``:
    over-predictions and within-margin under-predictions are covered by
    the provisioned t% margin, so only deeper misses are 'flying blind'."""
    cfg = pred.PredictorConfig(kind="persistence", n_bins=10,
                               warmup_steps=0, margin_bins=2)
    state = pred.init_state(cfg)
    # persistence predicts last bin; drive (predicted, actual) pairs:
    cases = [
        (9, 9, 0, 0),   # exact hit
        (9, 5, 1, 0),   # over-prediction: exact miss, margin covers
        (5, 7, 1, 0),   # under by 2 = margin_bins: still covered
        (7, 3, 1, 0),   # over again
        (3, 6, 1, 1),   # under by 3 > margin_bins: margin miss
    ]
    exact = margin = 0
    for predicted, actual, d_exact, d_margin in cases:
        p = pred.predict(cfg, state)
        assert int(p) == predicted
        state = pred.observe(cfg, state, jnp.asarray(_bin_w(actual, 10)), p)
        exact += d_exact
        margin += d_margin
        assert int(state.mispredictions) == exact
        assert int(state.margin_misses) == margin


def test_margin_miss_implies_exact_miss():
    """margin_misses ⊆ mispredictions on any trace, any family."""
    rng = np.random.default_rng(2)
    trace = rng.random(300).astype(np.float32)
    for kind in pred.available():
        cfg = pred.PredictorConfig(kind=kind, n_bins=12, warmup_steps=8,
                                   margin_bins=1)
        ev = pred.evaluate_trace(cfg, trace)
        assert (int(ev.final_state.margin_misses)
                <= int(ev.final_state.mispredictions))
        assert float(ev.margin_accuracy) >= float(ev.exact_accuracy)


def test_evaluate_trace_accuracies_match_counters():
    trace = np.abs(np.sin(np.linspace(0, 9, 200))).astype(np.float32)
    cfg = pred.PredictorConfig(kind="ewma", n_bins=10, warmup_steps=16,
                               margin_bins=1)
    ev = pred.evaluate_trace(cfg, trace)
    n_scored = len(trace) - cfg.warmup_steps
    assert float(ev.exact_accuracy) == pytest.approx(
        1.0 - int(ev.final_state.mispredictions) / n_scored)
    assert float(ev.margin_accuracy) == pytest.approx(
        1.0 - int(ev.final_state.margin_misses) / n_scored)
    # per-step arrays agree with the counters
    preds = np.asarray(ev.predicted)[cfg.warmup_steps:]
    acts = np.asarray(ev.actual)[cfg.warmup_steps:]
    assert int(ev.final_state.mispredictions) == int((preds != acts).sum())
    assert int(ev.final_state.margin_misses) == int(
        (acts > preds + cfg.margin_bins).sum())


# ---------------------------------------------------------------------------
# Family behavior
# ---------------------------------------------------------------------------


def test_markov_learns_deterministic_cycle():
    """A periodic bin sequence is predicted perfectly after training."""
    cfg = pred.PredictorConfig(kind="markov", n_bins=4, warmup_steps=8)
    cycle = [0.1, 0.35, 0.6, 0.85]  # bins 0,1,2,3 repeating
    trace = cycle * 32
    state, preds = _run(cfg, trace)
    actual = np.asarray([int(pred.workload_to_bin(jnp.asarray(w), 4))
                         for w in trace])
    assert (preds[-32:] == actual[-32:]).mean() == 1.0


def test_transition_matrix_row_stochastic():
    cfg = pred.PredictorConfig(kind="markov", n_bins=6)
    rng = np.random.default_rng(0)
    state, _ = _run(cfg, rng.random(200))
    for arg in (state, state.inner):  # wrapper and bare inner both work
        P = np.asarray(pred.transition_matrix(arg))
        assert np.allclose(P.sum(axis=1), 1.0, atol=1e-5)
        assert (P >= 0).all()


def test_markov_misprediction_counting_and_state_correction():
    cfg = pred.PredictorConfig(kind="markov", n_bins=4, warmup_steps=0)
    state = pred.init_state(cfg)
    p = pred.predict(cfg, state)
    wrong = (int(p) + 2) % 4
    state = pred.observe(cfg, state, jnp.asarray(_bin_w(wrong, 4)), p)
    assert int(state.mispredictions) == 1
    # state corrected to the actual bin (§V)
    assert int(state.inner.current_bin) == wrong


def test_markov_warmup_disagreements_reach_threshold_counter():
    """Warmup is not *scored*, but threshold-mode flushing still sees
    every disagreement (warmup observations keep training the model)."""
    cfg = pred.PredictorConfig(kind="markov", n_bins=8, warmup_steps=10,
                               update_mode="threshold",
                               mispred_threshold=100)
    state = pred.init_state(cfg)
    for _ in range(10):
        p = pred.predict(cfg, state)
        state = pred.observe(cfg, state, jnp.asarray(_bin_w(2, 8)), p)
    assert int(state.mispredictions) == 0
    assert int(state.inner.consecutive_mispred) == 10


def _dense_markov_chain(cfg, trace):
    """The Markov family as a plain NumPy float32 chain over a dense
    ``[M, M]`` count matrix, with the shell's warmup pinning and
    clipping.  Sums run left to right, as the compiled reductions do.
    The threshold-mode flush ``counts · decay + pending`` is rounded
    once: XLA's CPU backend fuses that multiply-add into one operation,
    at the parent's dense layout as at the flat one."""
    m, f32 = cfg.n_bins, np.float32
    counts = f32(0.01) * np.ones((m, m), f32) + np.eye(m, dtype=f32)
    pending = np.zeros((m, m), f32)
    cur, consecutive, preds = 0, 0, []
    for t, w in enumerate(trace):
        row = counts[cur]
        total = f32(0)
        for v in row:
            total = f32(total + v)
        probs = row / total
        if cfg.policy == "argmax":
            raw = int(np.argmax(probs))
        elif cfg.policy == "expected":
            mean = f32(0)
            for j, p in enumerate(probs):
                mean = f32(mean + p * f32(j))
            raw = int(np.ceil(mean))
        else:
            cdf = np.cumsum(probs, dtype=f32)
            raw = int(np.argmax(cdf >= f32(cfg.quantile)))
        p = m - 1 if t < cfg.warmup_steps else min(max(raw, 0), m - 1)
        preds.append(p)
        actual = min(max(int(np.floor(f32(w) * f32(m))), 0), m - 1)
        consecutive = consecutive + 1 if p != actual else 0
        if cfg.update_mode == "always":
            counts = counts * f32(cfg.count_decay)
            counts[cur, actual] += f32(1)
        else:
            pending[cur, actual] += f32(1)
            if consecutive >= cfg.mispred_threshold:
                counts = (counts.astype(np.float64) * f32(cfg.count_decay)
                          + pending).astype(f32)
                pending[:] = 0
                consecutive = 0
        cur = actual
    return np.asarray(preds), counts


@pytest.mark.parametrize("decay", [1.0, 0.95])
@pytest.mark.parametrize("update_mode", ["always", "threshold"])
@pytest.mark.parametrize("policy", ["argmax", "quantile", "expected"])
def test_markov_matches_dense_numpy_chain(policy, update_mode, decay):
    """The flat ``[M·M]`` counts, read and updated through one-hot
    masks, step exactly as a dense ``[M, M]`` chain: the same predicted
    bin at every step and the same final counts, bit for bit."""
    rng = np.random.default_rng(7)
    walk = np.abs((0.5 + np.cumsum(rng.normal(0.0, 0.08, 400))) % 2.0)
    trace = np.where(walk > 1.0, 2.0 - walk, walk).astype(np.float32)
    cfg = pred.PredictorConfig(kind="markov", n_bins=12, warmup_steps=16,
                               policy=policy, update_mode=update_mode,
                               count_decay=decay)
    ev = pred.evaluate_trace(cfg, trace)
    preds, counts = _dense_markov_chain(cfg, trace)
    assert len(set(preds)) > 6  # the chain visits most of its bins
    np.testing.assert_array_equal(np.asarray(ev.predicted), preds)
    np.testing.assert_array_equal(
        np.asarray(ev.final_state.inner.counts).reshape(12, 12), counts)


def test_quantile_policy_is_more_conservative():
    """Beyond-paper: the quantile policy never under-predicts more often
    than argmax on a noisy trace."""
    rng = np.random.default_rng(1)
    trace = np.clip(0.5 + 0.15 * rng.standard_normal(400), 0, 1)
    cfg_a = pred.PredictorConfig(kind="markov", n_bins=10, warmup_steps=16,
                                 policy="argmax")
    cfg_q = pred.PredictorConfig(kind="markov", n_bins=10, warmup_steps=16,
                                 policy="quantile", quantile=0.9)
    _, pa = _run(cfg_a, trace)
    _, pq = _run(cfg_q, trace)
    actual = (trace * 10).astype(int).clip(0, 9)
    assert (pq < actual).mean() <= (pa < actual).mean() + 1e-9


def test_persistence_predicts_last_bin():
    cfg = pred.PredictorConfig(kind="persistence", n_bins=10,
                               warmup_steps=0)
    state = pred.init_state(cfg)
    for b in (3, 7, 0, 9):
        state = pred.observe(cfg, state, jnp.asarray(_bin_w(b, 10)),
                             pred.predict(cfg, state))
        assert int(pred.predict(cfg, state)) == b


def test_ewma_tracks_step_change():
    """After a level shift the EWMA converges to the new bin."""
    cfg = pred.PredictorConfig(kind="ewma", n_bins=10, warmup_steps=0,
                               ewma_alpha=0.5)
    trace = [0.25] * 20 + [0.85] * 20
    state, preds = _run(cfg, trace)
    assert preds[15] == 2   # settled on the low level
    assert preds[-1] == 8   # converged to the high level


def test_holt_winters_anticipates_ramp():
    """The trend term lets HW lead a steady ramp; a trendless EWMA lags
    it — HW must under-predict strictly less often."""
    trace = np.linspace(0.1, 0.9, 120).astype(np.float32)
    kw = dict(n_bins=20, warmup_steps=8, margin_bins=0)
    hw = pred.evaluate_trace(
        pred.PredictorConfig(kind="holt_winters", **kw), trace)
    ew = pred.evaluate_trace(
        pred.PredictorConfig(kind="ewma", ewma_alpha=0.35, **kw), trace)
    assert (int(hw.final_state.margin_misses)
            < int(ew.final_state.margin_misses))


def test_holt_winters_seasonal_beats_nonseasonal_on_periodic_trace():
    period = 16
    t = np.arange(512)
    trace = (0.5 + 0.4 * np.sin(2 * np.pi * t / period)).astype(np.float32)
    kw = dict(n_bins=10, warmup_steps=2 * period)
    seas = pred.evaluate_trace(
        pred.PredictorConfig(kind="holt_winters", season=period, **kw),
        trace)
    flat = pred.evaluate_trace(
        pred.PredictorConfig(kind="holt_winters", season=0, **kw), trace)
    assert float(seas.exact_accuracy) > float(flat.exact_accuracy)


def test_hierarchy_weights_hurst_limits():
    """H→0.5 collapses to the shortest-scale EWMA; H→1 weights all
    scales equally (ω_j ∝ scale^(2H-2))."""
    from repro.core.predictors.hierarchy import _weights
    lo = pred.PredictorConfig(kind="hierarchy", hurst=0.5)
    hi = pred.PredictorConfig(kind="hierarchy", hurst=1.0)
    omega_lo, g_lo = _weights(lo)
    omega_hi, g_hi = _weights(hi)
    assert g_lo == 0.0 and g_hi == 1.0
    assert np.allclose(omega_hi, 1.0 / len(hi.hier_scales))
    assert omega_lo[0] > omega_lo[-1]  # short scales dominate at low H


def test_hierarchy_config_for_trace_measures_hurst():
    from repro.core import workload as wl
    cfg = pred.PredictorConfig(kind="hierarchy", hurst=0.76)
    trace = wl.fgn(n=2048, hurst=0.9, rng=np.random.default_rng(0))
    fitted = pred.config_for_trace(cfg, trace)
    assert fitted.hurst != cfg.hurst
    assert 0.5 <= fitted.hurst <= 1.0
    # too short to estimate → NaN → keep the configured default
    assert pred.config_for_trace(cfg, np.ones(8)).hurst == cfg.hurst


# ---------------------------------------------------------------------------
# Property test: every registered family returns valid bins
# ---------------------------------------------------------------------------


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(ws=st.lists(st.floats(min_value=0.0, max_value=1.0), min_size=5,
                       max_size=40),
           kind=st.sampled_from(sorted(pred.available())))
    def test_bins_always_valid_every_family(ws, kind):
        """Any reachable state of any registered predictor yields bins in
        [0, n_bins) — including out-of-range forecasts (clipped by the
        shared shell)."""
        cfg = pred.PredictorConfig(kind=kind, n_bins=10, warmup_steps=2)
        state, preds = _run(cfg, ws)
        assert ((preds >= 0) & (preds < 10)).all()
        assert int(state.steps) == len(ws)


def test_periodic_predictor_learns_period():
    period = 8
    state = pred.init_periodic(period)
    trace = [0.1 * (i % period) for i in range(64)]
    errs = []
    for w in trace:
        guess = pred.periodic_predict(state, period)
        errs.append(abs(float(guess) - w))
        state = pred.periodic_observe(state, jnp.asarray(w), period)
    assert np.mean(errs[-16:]) < 0.02


def test_register_rejects_duplicates_and_blank_names():
    class Dummy(pred.Predictor):
        name = "markov"  # collides

    with pytest.raises(ValueError, match="already registered"):
        pred.register(Dummy())
    Dummy.name = ""
    with pytest.raises(ValueError, match="non-empty"):
        pred.register(Dummy())


def test_seasonal_naive_exact_phase_hands_back_margin():
    """On an exactly tiled trace the ring reproduces every bin after one
    full period, and the predictor hands the controller's margin back:
    predictions sit ``margin_bins`` below the actual bin (clipped at 0),
    so exact-bin misses are by design while margin misses are zero."""
    period = 8
    pattern = [0.05, 0.15, 0.35, 0.55, 0.75, 0.95, 0.45, 0.25]
    trace = pattern * 6
    cfg = pred.PredictorConfig(kind="seasonal_naive", n_bins=10,
                               season=period, warmup_steps=period,
                               margin_bins=1)
    _, preds = _run(cfg, trace)
    actual = [min(int(w * 10), 9) for w in trace]
    for t in range(period, len(trace)):
        assert preds[t] == max(actual[t] - 1, 0), t
    ev = pred.evaluate_trace(cfg, np.asarray(trace, np.float32))
    assert int(ev.final_state.margin_misses) == 0
    assert int(ev.final_state.mispredictions) > 0   # handback by design


def test_seasonal_detect_period_and_config_for_trace():
    from repro.core.predictors import seasonal
    tiled = np.tile(np.linspace(0.1, 0.9, 12).astype(np.float32), 5)
    assert seasonal.detect_period(tiled) == 12
    rng = np.random.default_rng(0)
    noise = rng.uniform(0.0, 1.0, 96).astype(np.float32)
    assert seasonal.detect_period(noise) == 0
    cfg = pred.PredictorConfig(kind="seasonal_naive", n_bins=10)
    assert seasonal.config_for_trace(cfg, tiled).season == 12
    assert seasonal.config_for_trace(cfg, noise).season == 0


def test_seasonal_envelope_fallback_never_underpredicts_decay():
    """Without a season the fallback is the upper envelope
    ``max(EWMA level, last w)`` — on a pure decay it can only
    over-provision, never fly blind."""
    trace = np.linspace(0.9, 0.1, 40).astype(np.float32)
    cfg = pred.PredictorConfig(kind="seasonal_naive", n_bins=10,
                               season=0, warmup_steps=1)
    _, preds = _run(cfg, trace)
    actual = np.minimum((trace * 10).astype(int), 9)
    assert (preds[1:] >= actual[1:]).all()
