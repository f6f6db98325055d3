"""Fused RMSNorm Pallas kernel (fwd + bwd) — the classic bandwidth win:
unfused, the norm reads x three times (mean-square, normalize, scale);
fused it reads once, computes in VMEM, writes once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rms_fwd_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    o_ref[...] = (x * inv * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_bwd_kernel(x_ref, s_ref, g_ref, dx_ref, ds_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = x * inv
    ds_ref[0] = jnp.sum(g * xhat, axis=0, keepdims=True).astype(ds_ref.dtype)
    gs = g * s
    # d/dx of xhat·s: inv·(gs − xhat·mean(gs⊙xhat))
    dx = inv * (gs - xhat * jnp.mean(gs * xhat, axis=-1, keepdims=True))
    dx_ref[...] = dx.astype(dx_ref.dtype)


def rmsnorm_fwd(x, scale, eps: float = 1e-6, *, block_rows: int = 256,
                interpret: bool = False):
    """x: [N, D]; scale: [D]."""
    N, D = x.shape
    block_rows = min(block_rows, N)
    assert N % block_rows == 0
    return pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(N // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        interpret=interpret,
    )(x, scale)


def rmsnorm_bwd(x, scale, g, eps: float = 1e-6, *, block_rows: int = 256,
                interpret: bool = False):
    """Returns (dx [N,D], dscale_partials [n_blocks, D])."""
    N, D = x.shape
    block_rows = min(block_rows, N)
    assert N % block_rows == 0
    nb = N // block_rows
    dx, ds = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, eps=eps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            # [nb, 1, D]: the block's minor dims (1, D) equal the array's
            pl.BlockSpec((1, 1, D), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), x.dtype),
            jax.ShapeDtypeStruct((nb, 1, D), jnp.float32),
        ],
        interpret=interpret,
    )(x, scale, g)
    return dx, ds.reshape(nb, D)
