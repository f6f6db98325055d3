"""Flash-decode Pallas kernel: one query token vs. a long KV cache.

Decode attention is HBM-bandwidth-bound (the cache read dominates); the
kernel streams the cache through VMEM in blocks, maintaining the online
max/denominator in scratch.  Grid: (batch, kv_head, cache_blocks) with
the cache-block axis innermost/sequential.  All query heads of one KV
head (the GQA group) are processed together — q block [G, hd] hits the
MXU as a tall-skinny GEMM against [block_k, hd].

Per-sequence valid lengths mask the tail block (continuous batching
serves sequences of different lengths from one padded cache); they are
scalar-prefetched into SMEM.  The kernel reads a head-major
``[B, KVH, S, hd]`` cache view so each block's two minor dims are
``(block_k, hd)``, as the TPU tiling requires.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, block_k: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    k_start = ki * block_k

    @pl.when(k_start < length)
    def _run():
        # operands enter the MXU in their own dtype, accumulating in f32
        q = q_ref[0, 0]                                      # [G, hd]
        k = k_ref[0, 0]                                      # [bk, hd]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _fini():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_decode(q, k_cache, v_cache, lengths, *, block_k: int = 512,
                 interpret: bool = False):
    """q: [B,H,hd]; caches: [B,S,KVH,hd]; lengths: [B] -> [B,H,hd]."""
    B, H, hd = q.shape
    _, S, KVH, _ = k_cache.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)
    grid = (B, KVH, S // block_k)
    qg = q.reshape(B, KVH, G, hd)

    kernel = functools.partial(_fd_kernel, scale=scale, block_k=block_k)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, ki, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, hd),
                             lambda b, h, ki, lens: (b, h, ki, 0)),
                pl.BlockSpec((1, 1, block_k, hd),
                             lambda b, h, ki, lens: (b, h, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, h, ki, lens: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k_cache.transpose(0, 2, 1, 3),
      v_cache.transpose(0, 2, 1, 3))
    return out.reshape(B, H, hd)
