"""Pallas kernel: fused masked voltage-grid sweep + per-bin argmin.

One grid cell per (platform ``p``, sweep row ``r``).  The cell evaluates
the platform's delay/power term library over the flattened (core × bram)
voltage grid *in VMEM*, applies the technique mask and the QoS timing
predicate for every frequency level of the row at once as an
``[M, G]`` tile, and reduces each level to its minimum-power feasible
grid point — the whole §V synthesis sweep is a single fused pass with no
``[P, R, M, C, B]`` intermediate ever touching HBM.

Layout notes:

* every operand carries a unit axis in front of its last dim (``[P, 1, D]``
  term libraries, ``[R, 1, G]`` masks, ``[P, R, 1, M]`` outputs) and the
  per-cell axes are squeezed out of the block, so each block's last two
  dims equal the array's — the layout rule Mosaic (the TPU lowering)
  enforces for blocks that are not (8, 128)-aligned;
* the (C × B) grid is flattened row-major and lane-padded to ``G``
  (multiple of 128); padded lanes carry ``mask=False`` so they can never
  win the argmin;
* frequency levels ride the sublane axis, padded to ``M`` (multiple
  of 8); padded levels are sliced off by ``ops.py``;
* the argmin keeps the *first* minimizing flat index (ties included),
  matching ``voltage.masked_grid_argmin``'s row-major tie-break, and the
  selected voltages are gathered with a one-hot contraction (TPU-safe —
  no dynamic gather);
* when no masked point meets timing the row falls back to the nominal
  grid corner (``flat index C·B−1`` — grids ascend), exactly like the
  reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import characterization as char

Array = jax.Array


def _grid_argmin_kernel(dl_weight, dl_vth, dl_alpha, dl_v0, dl_rail,
                        delay_mode, pw_rail, pw_v0, pw_dyn, pw_stat,
                        pw_kappa, mask, levels, vc_flat, vb_flat,
                        v_core_out, v_bram_out, power_out, feas_out,
                        *, g_nominal: int, slack_eps: float):
    """One (platform, row) cell: [M, G] feasibility/objective + argmin."""
    vc = vc_flat[0, :]                                    # [G]
    vb = vb_flat[0, :]
    msk = mask[0, :] != 0                                 # [G] bool
    f = levels[0, :]                                      # [M]
    m_levels, g = f.shape[0], vc.shape[0]

    # --- delay(Vc, Vb) over the grid: combine the padded term library ---
    w = dl_weight[0, :][:, None]                          # [D, 1]
    vth = dl_vth[0, :][:, None]
    alpha = dl_alpha[0, :][:, None]
    v0 = dl_v0[0, :][:, None]
    v = jnp.where(dl_rail[0, :][:, None] == char.RAIL_CORE,
                  vc[None, :], vb[None, :])               # [D, G]
    num = v / jnp.maximum(v - vth, 1e-6) ** alpha
    den = v0 / (v0 - vth) ** alpha
    terms = w * (num / den)
    delay = jnp.where(delay_mode[0, 0] == 1,
                      jnp.max(terms, axis=0), jnp.sum(terms, axis=0))  # [G]

    # --- power split into f-independent dyn/stat grid sums ---
    pv0 = pw_v0[0, :][:, None]                            # [T, 1]
    prail = pw_rail[0, :][:, None]
    pv = jnp.where(prail == char.RAIL_CORE, vc[None, :],
                   jnp.where(prail == char.RAIL_BRAM, vb[None, :], pv0))
    dyn = jnp.sum(pw_dyn[0, :][:, None] * (pv / pv0) ** 2, axis=0)     # [G]
    stat = jnp.sum(pw_stat[0, :][:, None] * (pv / pv0)
                   * jnp.exp(pw_kappa[0, :][:, None] * (pv - pv0)),
                   axis=0)                                             # [G]

    # --- per-level masked argmin as one [M, G] tile ---
    stretch = 1.0 / jnp.maximum(f, 1e-6)                  # [M]
    feas = ((delay[None, :] <= stretch[:, None] * (1.0 + slack_eps))
            & msk[None, :])                               # [M, G]
    obj = dyn[None, :] * f[:, None] + stat[None, :]
    masked = jnp.where(feas, obj, jnp.inf)
    idx = jnp.argmin(masked, axis=1)                      # [M] first-min ties
    any_f = jnp.any(feas, axis=1)                         # [M]

    onehot = (jax.lax.broadcasted_iota(jnp.int32, (m_levels, g), 1)
              == idx[:, None])
    pick = lambda x: jnp.sum(jnp.where(onehot, x[None, :], 0.0), axis=1)
    p_nom = dyn[g_nominal] * f + stat[g_nominal]

    v_core_out[0, :] = jnp.where(any_f, pick(vc), vc[g_nominal])
    v_bram_out[0, :] = jnp.where(any_f, pick(vb), vb[g_nominal])
    power_out[0, :] = jnp.where(any_f, jnp.min(masked, axis=1), p_nom)
    feas_out[0, :] = any_f.astype(jnp.float32)


def grid_argmin_fwd(params: char.PlatformParams, masks_flat: Array,
                    levels: Array, vc_flat: Array, vb_flat: Array,
                    *, g_nominal: int, slack_eps: float = 1e-6,
                    interpret: bool = False):
    """Launch the sweep: ``params`` [P, ...], ``masks_flat`` [R, G] int32
    (lane-padding already False), ``levels`` [R, M] (sublane-padded),
    ``vc_flat``/``vb_flat`` [1, G].  Returns four [P, R, M] arrays
    ``(v_core, v_bram, power, feasible_f32)``.
    """
    n_p = params.dl_weight.shape[0]
    n_r, g = masks_flat.shape
    m = levels.shape[1]
    d = params.dl_weight.shape[1]
    t = params.pw_dyn.shape[1]

    # [N, w] operands gain a unit axis ([N, 1, w]); the squeezed (None)
    # leading block dim leaves the kernel a [1, w] ref whose block equals
    # the array's last two dims.
    plat = lambda w: pl.BlockSpec((None, 1, w), lambda p, r: (p, 0, 0))
    row = lambda w: pl.BlockSpec((None, 1, w), lambda p, r: (r, 0, 0))
    shared = pl.BlockSpec((1, g), lambda p, r: (0, 0))
    out = pl.BlockSpec((None, None, 1, m), lambda p, r: (p, r, 0, 0))
    unit = lambda x: x[:, None, :]

    kernel = functools.partial(_grid_argmin_kernel, g_nominal=g_nominal,
                               slack_eps=slack_eps)
    shape = jax.ShapeDtypeStruct((n_p, n_r, 1, m), jnp.float32)
    outs = pl.pallas_call(
        kernel,
        grid=(n_p, n_r),
        in_specs=[plat(d)] * 5                                  # delay terms
        + [plat(1)]                                             # delay_mode
        + [plat(t)] * 5                                         # power terms
        + [row(g), row(m)]                                      # mask, levels
        + [shared] * 2,                                         # vc, vb
        out_specs=[out] * 4,
        out_shape=[shape] * 4,
        interpret=interpret,
        name="grid_argmin",   # the trace's `%grid_argmin.N` operation
    )(*map(unit, (params.dl_weight, params.dl_vth, params.dl_alpha,
                  params.dl_v0, params.dl_rail,
                  params.delay_mode.reshape(n_p, 1).astype(jnp.int32),
                  params.pw_rail, params.pw_v0, params.pw_dyn,
                  params.pw_stat, params.pw_kappa, masks_flat, levels)),
      vc_flat, vb_flat)
    return tuple(x[:, :, 0] for x in outs)
