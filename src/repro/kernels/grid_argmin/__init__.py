"""Fused masked voltage-grid sweep + per-bin argmin (the §V cold path).

The fleet table builder (``controller.fleet_bin_tables``) sweeps every
platform × technique-row × frequency-level over the shared (core × bram)
voltage grid and keeps each level's minimum-power feasible point.  This
package fuses that sweep into one Pallas kernel:

  kernel.py — ``pl.pallas_call`` grid over (platform, row); the delay /
      power term library, technique mask, QoS timing predicate, and the
      per-level argmin all evaluate in VMEM as one [levels × grid] tile.
  ops.py    — jit'd public ``grid_argmin``; the kernel when lowered
      for a TPU, the lax reference elsewhere, interpret mode via
      ``impl="interpret"`` (CI parity tests).
  ref.py    — ``grid_argmin_ref``: the pre-kernel vmap pyramid over
      ``voltage.optimize_point_params`` (single source of truth through
      ``voltage.masked_grid_argmin``).
"""

from repro.kernels.grid_argmin.ops import grid_argmin, grid_argmin_ref

__all__ = ["grid_argmin", "grid_argmin_ref"]
