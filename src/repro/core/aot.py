"""AOT cold-path pipeline: persistent compilation cache + program warmers.

The fleet engine's *warm* path is microseconds, but its *cold* path —
tracing and XLA-compiling the two fleet programs (the grid-sweep tables
program and the streaming chunk program) — costs seconds per process.
This module makes that cost a one-time, machine-wide expense:

* :func:`enable_compilation_cache` turns on JAX's persistent
  compilation cache (``chip_smoke.py``, ``scripts/campaign.py``,
  ``scripts/compose.py`` and ``benchmarks/run.py`` call it at start-up);
  every XLA compile after that is written to / served from disk, so a
  process that re-runs a previously-seen program shape only pays the
  (cheap) trace.  The directory is ``$JAX_COMPILATION_CACHE_DIR`` when
  that is set, else the fixed ``<checkout>/.jax-cache``.
* :func:`warm_fleet_programs` ahead-of-time ``jit(...).lower(...)
  .compile()``\\ s both fleet programs for a given fleet shape — at setup
  time, not first-use time — populating the in-memory executable *and*
  the persistent cache.  Shapes come from the same helpers the live path
  uses (``controller._sweep_rows``), so the warmed programs are
  byte-identical to the ones ``fleet_bin_tables`` /
  ``simulate_fleet_stream`` will ask for.

Nothing here runs at import time: call sites opt in explicitly.
"""

from __future__ import annotations

import os
import pathlib
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import controller as ctl
from repro.core import predictors as pred_mod
from repro.core import characterization as char
from repro.core import scheduler as sched_mod

#: Cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path in the checkout (gitignored), so it is the same on every run.
DEFAULT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                        / ".jax-cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache goes to the fixed
    :data:`DEFAULT_CACHE_DIR`.  Zeroes the min-compile-time /
    min-entry-size gates so the fleet programs (sub-second compiles on
    CPU) are cached too.  Idempotent.  The directory is shared across
    processes and reused across runs — that is the point: the second
    process's "cold" call skips XLA compilation entirely.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    return cache_dir


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
        tree)


def tables_program_args(params: char.PlatformParams,
                        cfg: ctl.ControllerConfig,
                        techniques: Sequence[str] = ctl.DEFAULT_TECHNIQUES
                        ) -> Tuple:
    """Abstract positional arguments of the grid-sweep tables program
    (``controller._fleet_dvfs_tables_jit``) for one fleet: the same
    ``_sweep_rows`` shapes :func:`~repro.core.controller.fleet_bin_tables`
    feeds it."""
    grids, _, row_masks, row_levels = ctl._sweep_rows(cfg, techniques)
    return tuple(_abstract(x) for x in (params, row_masks, row_levels,
                                        grids.core, grids.bram))


def stream_program_args(cfg: ctl.ControllerConfig, k: int,
                        chunk_size: int = 1024, n_tenants: int = 1
                        ) -> Tuple:
    """Abstract array arguments of the streaming chunk program
    (``controller._fleet_stream_chunk_jit``) at fleet size ``k``, chunk
    ``chunk_size`` and tenant width ``n_tenants`` — everything but the
    static ``cfg`` / ``emit``, in call order."""
    m = cfg.n_bins
    c = max(1, int(chunk_size))
    q = max(1, int(n_tenants))
    f32 = jnp.float32
    # Per-bin [K, M] fields, except the per-cell scalar headroom [K].
    flat = ctl.BinTables(*[jax.ShapeDtypeStruct(
        (k,) if f == "headroom" else (k, m), f32)
        for f in ctl.BinTables._fields])

    # state_spec is already abstract (no concrete state materializes on
    # the cold path) — only the fleet axis K is prepended here.
    def _cell_states(pcfg):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((k,) + x.shape, x.dtype),
            pred_mod.state_spec(pcfg))

    spec = sched_mod.TenantSpec(*[jax.ShapeDtypeStruct((k, q), f32)
                                  for _ in sched_mod.TenantSpec._fields])
    return (flat, _cell_states(cfg.predictor),
            _cell_states(cfg.avail_predictor),
            jax.ShapeDtypeStruct((k, q), f32),
            jax.ShapeDtypeStruct((k, q), f32),
            jax.ShapeDtypeStruct((k, c, q), f32),
            jax.ShapeDtypeStruct((k, c), f32),
            jax.ShapeDtypeStruct((c,), jnp.bool_), spec,
            jax.ShapeDtypeStruct((3,), f32))


def warm_fleet_programs(params: char.PlatformParams,
                        cfg: ctl.ControllerConfig,
                        techniques: Sequence[str] = ctl.DEFAULT_TECHNIQUES,
                        *, fleet_shape: Optional[Tuple[int, ...]] = None,
                        chunk_size: int = 1024, n_tenants: int = 1,
                        emit: Sequence[str] = ()) -> Dict[str, float]:
    """AOT-compile the two fleet programs for one fleet shape.

    ``fleet_shape`` is the tables' leading axes as seen by
    :func:`~repro.core.controller.simulate_fleet_stream` — default
    ``(P, len(techniques))``; pass e.g. ``(P, T, N)`` for a campaign
    with a scenario axis.  ``n_tenants`` is the tenant-axis width of
    the workload plane (1 for aggregate runs; tenant campaigns pad to
    a common width, so warm once at that width).  Lowering uses
    abstract values only (no table math runs); ``.compile()``
    populates the persistent cache when
    :func:`enable_compilation_cache` is active.  Returns wall-clock
    seconds per program: ``{"tables_compile_s", "stream_compile_s"}``.
    """
    t0 = time.perf_counter()
    ctl._fleet_dvfs_tables_jit.lower(
        *tables_program_args(params, cfg, techniques)).compile()
    t_tables = time.perf_counter() - t0

    # The streaming chunk program is keyed on (K, C) + cfg.
    if fleet_shape is None:
        fleet_shape = (int(params.watts_scale.shape[0]), len(techniques))
    k = 1
    for dim in fleet_shape:
        k *= int(dim)
    t0 = time.perf_counter()
    ctl._fleet_stream_chunk_jit.lower(
        *stream_program_args(cfg, k, chunk_size, n_tenants),
        ctl._runtime_cfg(cfg), tuple(emit)).compile()
    t_stream = time.perf_counter() - t0
    return {"tables_compile_s": t_tables, "stream_compile_s": t_stream}
