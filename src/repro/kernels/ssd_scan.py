"""Mamba2 SSD intra-chunk Pallas kernel (TPU target).

The SSD "dual form" makes the intra-chunk computation a pair of GEMMs
plus a masked decay product — ideal MXU work.  This kernel computes, per
(batch, chunk, head-block):

    y_intra = ((C·Bᵀ) ⊙ L) · (dt⊙x)      (quadratic-within-chunk term)
    state   = (decay_out ⊙ dt⊙x)ᵀ · B     (chunk's emitted state)

The inter-chunk recurrence (linear scan over chunks) stays outside in
jnp — it is O(S/Q) sequential steps on [nh, hp, ds] tensors and fuses
fine in XLA; the quadratic work is what needs VMEM tiling.

Grid: (B, head_blocks); one chunk's [Q, ·] tensors are VMEM blocks
(Q = 128–256 aligns the GEMMs to the MXU).  The kernel works on
head-major views (x ``[B, nh, Q, hp]``, dt ``[B, nh, Q]``, Bᵀ
``[B, ds, Q]``) so every block's two minor dims meet the TPU tiling, and
loops over the block's heads with 2-D GEMMs only; the wrapper transposes
in and out.  The within-chunk cumulative sum of ``dt·A`` is a GEMM
against a lower-triangular ones matrix, transposed for its column view.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HI = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, bt_ref, c_ref, dt_ref, alog_ref, y_ref, st_ref,
                dec_ref, *, block_h: int, q: int):
    # blocks: x [1,bh,Q,hp]; bt [1,ds,Q]; c [1,Q,ds]; dt [1,bh,Q];
    # alog [bh,1]; outputs y [1,bh,Q,hp], stᵀ [1,bh,ds,hp], dec [1,bh,1]
    bt = bt_ref[0].astype(jnp.float32)        # [ds, Q]
    cm = c_ref[0].astype(jnp.float32)         # [Q, ds]
    dt = dt_ref[0].astype(jnp.float32)        # [bh, Q]
    a = -jnp.exp(alog_ref[...].astype(jnp.float32))   # [bh, 1]
    dA = dt * a                               # [bh, Q]

    iota_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = (iota_i >= iota_j).astype(jnp.float32)      # [Q, Q] lower
    nt = (((1,), (1,)), ((), ()))             # contract both minor dims
    cum = jax.lax.dot_general(dA, tri, nt, precision=_HI,
                              preferred_element_type=jnp.float32)  # [bh, Q]
    # the column view must be the SAME numbers: seg = cum_i - cum_j
    # cancels, and only a shared rounding keeps it exact near the diagonal
    cum_t = cum.T                                                  # [Q, bh]
    cb = jnp.dot(cm, bt, precision=_HI,
                 preferred_element_type=jnp.float32)  # [Q, Q]

    for h in range(block_h):
        x = x_ref[0, h].astype(jnp.float32)   # [Q, hp]
        dt_h = dt[h:h + 1, :]                 # [1, Q]
        cum_h = cum[h:h + 1, :]               # [1, Q]
        seg = cum_t[:, h:h + 1] - cum_h       # [Q, Q]: cum_i - cum_j
        w = cb * jnp.exp(jnp.clip(seg, -60.0, 0.0)) * tri * dt_h
        y_ref[0, h] = jnp.dot(w, x, precision=_HI,
                              preferred_element_type=jnp.float32
                              ).astype(y_ref.dtype)
        decay_out = jnp.exp(jnp.clip(cum_h[:, q - 1:] - cum_h, -60.0, 0.0))
        st_ref[0, h] = jnp.dot(bt * (dt_h * decay_out), x, precision=_HI,
                               preferred_element_type=jnp.float32)  # [ds, hp]
    dec_ref[0] = jnp.exp(jnp.clip(cum[:, q - 1:], -60.0, 0.0))


def ssd_chunk(x, b, c, dt, a_log, *, block_h: int = 8, interpret: bool = False):
    """Intra-chunk SSD for stacked chunks.

    x: [B,Q,nh,hp]; b,c: [B,Q,ds]; dt: [B,Q,nh]; a_log: [nh].
    Returns (y_intra [B,Q,nh,hp], states [B,nh,hp,ds], decay_total [B,nh]).
    """
    B, Q, nh, hp = x.shape
    ds = b.shape[-1]
    block_h = min(block_h, nh)
    assert nh % block_h == 0
    grid = (B, nh // block_h)

    kernel = functools.partial(_ssd_kernel, block_h=block_h, q=Q)
    y, st, dec = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_h, Q, hp), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, ds, Q), lambda bi, hi: (bi, 0, 0)),
            pl.BlockSpec((1, Q, ds), lambda bi, hi: (bi, 0, 0)),
            pl.BlockSpec((1, block_h, Q), lambda bi, hi: (bi, hi, 0)),
            pl.BlockSpec((block_h, 1), lambda bi, hi: (hi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_h, Q, hp), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, block_h, ds, hp), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, block_h, 1), lambda bi, hi: (bi, hi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, Q, hp), x.dtype),
            jax.ShapeDtypeStruct((B, nh, ds, hp), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), b.transpose(0, 2, 1), c,
      dt.transpose(0, 2, 1), a_log.reshape(nh, 1))
    return (y.transpose(0, 2, 1, 3), st.transpose(0, 1, 3, 2),
            dec.reshape(B, nh))
