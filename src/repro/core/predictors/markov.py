"""Markov-chain workload predictor (paper §IV-A, §V).

Discrete-time Markov chain over ``M`` workload bins.  Transition counts
are learned online; prediction reads the current bin's transition row
under the configured policy.  The paper's policy is ``argmax``; two
beyond-paper variants ride the same counts:

* ``quantile`` — smallest bin whose cumulative transition probability
  exceeds ``q`` (trades a little power for fewer QoS violations);
* ``expected`` — conservative ceil of the expected next bin.

Misprediction handling (§V): the chain's state is always corrected to
the *actual* bin; in ``threshold`` update mode edge counts are only
flushed into the model after ``mispred_threshold`` consecutive
mispredictions (the paper's lazy re-learning), while ``always`` mode
learns every transition immediately.

The counts are stored flat, ``[M·M]`` row-major (entry ``i·M + j`` is
the edge i → j), and no step indexes them by a traced bin: the current
row is read through a one-hot row mask, and the edge is added where a
one-hot compare over the flat axis holds.  Under ``vmap`` over a fleet
an indexed row read lowers to a gather and an indexed edge add to a
scatter, which want different layouts of the carried ``[K, M, M]``
counts, so the compiler relaid the whole state out every step.  Masks
move no value, so the chain and its predictions are those of the
indexed form, bit for bit.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp

from repro.core.predictors.base import (Array, Predictor, PredictorConfig,
                                        register)


class MarkovInner(NamedTuple):
    counts: Array          # [M·M] transition counts, row-major (float32)
    pending: Array         # [M·M] counts awaiting threshold flush
    current_bin: Array     # int32 — bin observed for the last completed step
    consecutive_mispred: Array  # int32 — for the threshold update mode


class MarkovPredictor(Predictor):
    name = "markov"

    def init_inner(self, cfg: PredictorConfig) -> MarkovInner:
        m = cfg.n_bins
        # Diagonal-biased Laplace prior: before any evidence, the best
        # guess is a self-transition (workloads are short-term sticky);
        # the small uniform floor keeps every edge alive, as in the
        # paper's fully-connected chain.
        prior = 0.01 * jnp.ones((m, m), jnp.float32) + \
            jnp.eye(m, dtype=jnp.float32)
        return MarkovInner(
            counts=prior.reshape(m * m),
            pending=jnp.zeros((m * m,), jnp.float32),
            current_bin=jnp.asarray(0, jnp.int32),
            consecutive_mispred=jnp.asarray(0, jnp.int32),
        )

    def predict_inner(self, cfg: PredictorConfig,
                      inner: MarkovInner) -> Array:
        m = cfg.n_bins
        # The current row: every other row masked to -inf, then the max
        # down each column.  (A sum over zeros would be exact too, but
        # the compiler merges it with the sum below into one reduction
        # over M·M values, in another order.)
        src = jnp.arange(m * m, dtype=jnp.int32) // m
        masked = jnp.where(src == inner.current_bin, inner.counts, -jnp.inf)
        row = jnp.max(masked.reshape(m, m), axis=0)
        probs = row / jnp.sum(row)
        if cfg.policy == "argmax":
            return jnp.argmax(probs).astype(jnp.int32)
        if cfg.policy == "expected":
            # conservative ceil of the expected bin
            exp_bin = jnp.sum(probs * jnp.arange(cfg.n_bins))
            return jnp.ceil(exp_bin).astype(jnp.int32)
        # "quantile" — config validation rejects anything else eagerly
        cdf = jnp.cumsum(probs)
        return jnp.argmax(cdf >= cfg.quantile).astype(jnp.int32)

    def observe_inner(self, cfg: PredictorConfig, inner: MarkovInner,
                      w: Array, actual_bin: Array,
                      predicted_bin: Array) -> MarkovInner:
        m = cfg.n_bins
        hit = jnp.arange(m * m, dtype=jnp.int32) == \
            inner.current_bin * m + actual_bin

        # The consecutive counter (which gates threshold-mode flushing)
        # sees every disagreement, warmup included — only the *score*
        # (in the shared shell) skips warmup, so observations reach the
        # model exactly as in the paper's online training.
        mispred = predicted_bin != actual_bin
        consecutive = jnp.where(mispred, inner.consecutive_mispred + 1,
                                jnp.asarray(0, jnp.int32))

        if cfg.update_mode == "always":
            # Decay, then add the edge: written as ``decayed + edge`` the
            # compiler may fuse the two into one multiply-add with one
            # rounding, an ulp away from the chain's decayed count.
            decayed = inner.counts * cfg.count_decay
            counts = jnp.where(hit, decayed + 1.0, decayed)
            pending = inner.pending
        else:
            flush = consecutive >= cfg.mispred_threshold
            pending_new = inner.pending + hit.astype(jnp.float32)
            counts = jnp.where(flush,
                               inner.counts * cfg.count_decay + pending_new,
                               inner.counts)
            pending = jnp.where(flush, jnp.zeros_like(pending_new),
                                pending_new)
            consecutive = jnp.where(flush, jnp.asarray(0, jnp.int32),
                                    consecutive)

        return MarkovInner(counts=counts, pending=pending,
                           current_bin=actual_bin,
                           consecutive_mispred=consecutive)


register(MarkovPredictor())


def transition_matrix(state) -> Array:
    """Row-stochastic transition probabilities P[i, j], as ``[M, M]``.

    Accepts either a wrapper ``PredictorState`` (kind="markov") or a
    bare :class:`MarkovInner`; leading (fleet) axes of the flat counts
    are kept.
    """
    inner = getattr(state, "inner", state)
    m = math.isqrt(inner.counts.shape[-1])
    counts = inner.counts.reshape(inner.counts.shape[:-1] + (m, m))
    return counts / jnp.sum(counts, axis=-1, keepdims=True)
