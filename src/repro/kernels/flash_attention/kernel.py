"""Pallas TPU flash-attention forward kernel.

Grid: (batch, kv_heads, q_blocks) with the KV axis walked *inside* the
kernel body via ``jax.lax.fori_loop`` over VMEM-resident blocks — the
online-softmax running (max, sum, acc) never leaves VMEM, so HBM traffic
is O(S·d) instead of the O(S²) score traffic the XLA path pays.

TPU mapping decisions (HW codesign):
  * block shapes are (block_q, head_dim) × (block_kv, head_dim) with
    head_dim padded to the 128-lane register width and block_q a multiple
    of 8 (fp32 sublanes) — MXU-aligned matmul tiles;
  * GQA is handled by loading one KV head per grid cell and the G query
    heads that share it folded into the q-block rows (q laid out
    [B, KV, G·Sq_blk, D]) — KV is read once per G query heads;
  * causal + sliding-window masking is applied with position iotas; KV
    blocks wholly outside the (causal, window) band are skipped by
    clamping the fori_loop bounds — triangular/banded work, not masked
    work;
  * optional gemma-style logit soft-capping fuses into the score tile.

Validated on CPU with ``interpret=True`` against ``ref.attention_ref``
(tests/test_kernels_flash.py sweeps shapes/dtypes); compiled path targets
real TPUs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.0e38


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
               causal: bool, window: Optional[int],
               softcap: Optional[float], block_kv: int, seq_kv: int,
               seq_q: int, block_q: int):
    """One (batch, kv-head, q-block) grid cell.

    q_ref: [block_q, D] — G query heads × q rows for this KV head.
    k_ref/v_ref: [seq_kv, D] in VMEM (whole KV stripe for this head).
    """
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * scale

    n_kv_blocks = seq_kv // block_kv
    # rows fold G query heads over Sq; the true sequence position is the
    # row index modulo seq_q (blocks never straddle heads: Sq % block_q == 0)
    q0 = (qi * block_q) % seq_q

    if causal:
        # last KV block that any row of this q block can see
        hi = jnp.minimum((q0 + block_q + block_kv - 1) // block_kv,
                         n_kv_blocks)
    else:
        hi = n_kv_blocks
    if window is not None:
        lo = jnp.maximum((q0 - window + 1) // block_kv, 0)
    else:
        lo = 0

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_kv), 0)
        kpos = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        keep = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            keep &= qpos >= kpos
        if window is not None:
            keep &= (qpos - kpos) < window
        s = jnp.where(keep, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[...] = out.astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None,
                        block_q: int = 128, block_kv: int = 128,
                        interpret: bool = False) -> jax.Array:
    """q: [B,Sq,H,D]; k,v: [B,Sk,KV,D]; H = KV·G.  Returns [B,Sq,H,Dv].

    Causal masking assumes right-aligned self-attention (Sq == Sk) when
    ``causal=True``.
    """
    B, Sq, H, D = q.shape
    _, Sk, KV, Dv = v.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    assert Sk % block_kv == 0, (Sk, block_kv)
    block_q = min(block_q, Sq)
    assert Sq % block_q == 0

    # layout: fold grouped query heads onto the row axis per KV head:
    # [B, KV, G*Sq, D] so one grid cell serves every head sharing its KV.
    qg = q.reshape(B, Sq, KV, G, D).transpose(0, 2, 3, 1, 4) \
          .reshape(B, KV, G * Sq, D)
    kk = k.transpose(0, 2, 1, 3)     # [B,KV,Sk,D]
    vv = v.transpose(0, 2, 1, 3)

    grid = (B, KV, (G * Sq) // block_q)
    # NB: with q rows folded as [g, Sq], a q block must not straddle two
    # heads: require Sq % block_q == 0 (asserted above) so blocks tile
    # heads cleanly, and recover the true q position modulo Sq.
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_kv=block_kv, seq_kv=Sk, seq_q=Sq,
        block_q=block_q)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, Sk, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((None, None, Sk, Dv), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, Dv),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G * Sq, Dv), q.dtype),
        interpret=interpret,
    )(qg, kk, vv)

    return out.reshape(B, KV, G, Sq, Dv).transpose(0, 3, 1, 2, 4) \
              .reshape(B, Sq, H, Dv)
