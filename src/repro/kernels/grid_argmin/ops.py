"""Public fused grid-argmin op (jit'd wrapper with backend dispatch).

``grid_argmin`` is the fleet table sweep's entry point.  By default the
program picks its implementation by the platform it is lowered for: the
Pallas kernel on TPU, the pure-lax reference elsewhere (the CPU, where
tier-1 CI runs).  An explicit ``impl=`` is the only override:
``"pallas"``, ``"ref"``, or ``"interpret"`` (the kernel body run by the
Pallas interpreter, so it is testable on any host).  All implementations
share
:func:`repro.core.voltage.masked_grid_argmin` semantics — first-flat-
index tie-break, nominal-corner fallback — and must agree to ≤ 1e-5.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import characterization as char
from repro.core import voltage as volt
from repro.kernels.grid_argmin.kernel import grid_argmin_fwd
from repro.kernels.grid_argmin.ref import grid_argmin_ref  # noqa: F401

Array = jax.Array

def _pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


@functools.partial(jax.jit, static_argnames=("slack_eps", "impl"))
def grid_argmin(params: char.PlatformParams, masks: Array, levels: Array,
                core_grid: Array, bram_grid: Array, *,
                slack_eps: float = 1e-6,
                impl: str | None = None) -> volt.OperatingPoint:
    """Fused masked grid sweep + per-bin argmin over a stacked fleet.

    ``params`` leaves ``[P, ...]``; ``masks`` ``[R, C, B]`` bool (one row
    per DVFS technique / hybrid gear); ``levels`` ``[R, M]``;
    ``core_grid``/``bram_grid`` the shared ascending voltage grids.
    Returns an :class:`~repro.core.voltage.OperatingPoint` with
    ``[P, R, M]`` fields.  jit-keyed on shapes only (zero-retrace
    contract — see ``controller.fleet_trace_counts``).  ``impl=None``
    lowers the kernel for a TPU and the reference for any other
    platform (``lax.platform_dependent``), so the choice follows the
    device the program runs on, not the process's default backend.
    """
    if impl is None:
        return jax.lax.platform_dependent(
            params, masks, levels, core_grid, bram_grid,
            tpu=functools.partial(grid_argmin, slack_eps=slack_eps,
                                  impl="pallas"),
            default=functools.partial(grid_argmin_ref, slack_eps=slack_eps))
    if impl == "ref":
        return grid_argmin_ref(params, masks, levels, core_grid, bram_grid,
                               slack_eps=slack_eps)

    c, b = core_grid.shape[0], bram_grid.shape[0]
    n_r, m = levels.shape[0], levels.shape[1]
    g_pad = _pad_to(c * b, 128)
    m_pad = _pad_to(m, 8)

    # Row-major flattening matches the reference's reshape(-1) argmin, so
    # the tie-break picks the identical grid point.  Padded lanes get the
    # nominal voltages but a False mask — they can never be selected.
    vc_flat = jnp.broadcast_to(core_grid[:, None], (c, b)).reshape(-1)
    vb_flat = jnp.broadcast_to(bram_grid[None, :], (c, b)).reshape(-1)
    # Edge-padding repeats the last row-major element — the nominal
    # (grid[-1], grid[-1]) corner — keeping padded lanes numerically tame.
    vc_flat = jnp.pad(vc_flat, (0, g_pad - c * b), mode="edge")[None, :]
    vb_flat = jnp.pad(vb_flat, (0, g_pad - c * b), mode="edge")[None, :]
    masks_flat = jnp.pad(masks.reshape(n_r, c * b).astype(jnp.int32),
                         ((0, 0), (0, g_pad - c * b)))
    # Padded levels re-run level 0 and are sliced off below.
    levels_pad = jnp.pad(levels.astype(jnp.float32),
                         ((0, 0), (0, m_pad - m)), mode="edge")

    v_core, v_bram, power, feas = grid_argmin_fwd(
        params, masks_flat, levels_pad, vc_flat, vb_flat,
        g_nominal=c * b - 1, slack_eps=slack_eps,
        interpret=(impl == "interpret"))
    f_rel = jnp.broadcast_to(levels.astype(jnp.float32)[None],
                             v_core[:, :, :m].shape)
    return volt.OperatingPoint(
        v_core=v_core[:, :, :m], v_bram=v_bram[:, :, :m], f_rel=f_rel,
        power=power[:, :, :m], feasible=feas[:, :, :m] > 0.5)
