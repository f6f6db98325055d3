"""Flash-attention forward Pallas kernel (TPU target).

TPU adaptation of the CUDA flash algorithm (DESIGN.md §6): the SRAM
tiling becomes explicit VMEM BlockSpecs; the MXU wants the [block_q,
head_dim] × [head_dim, block_k] GEMM shapes aligned to 128; the running
max/denominator live in VMEM scratch across the kv-block grid dimension
(sequential innermost grid axis on TPU), replacing the CUDA thread-block
reduction.

Grid: (batch, heads, q_blocks, kv_blocks) — kv innermost/sequential.
The kernel works on a head-major ``[B, H, S, hd]`` view, so each block's
two minor dims are ``(block, hd)`` as the TPU tiling requires; the
wrapper transposes the model's ``[B, S, H, hd]`` layout in and out.
GQA is handled in the q-head → kv-head index map (no KV repeat in HBM).
Causality is exploited by masking; fully-masked kv blocks are skipped
via ``pl.when`` (the 2× causal FLOP saving).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, block_q: int, block_k: int, causal: bool,
               sq: int, sk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal alignment: q position i is absolute position i + (sk - sq),
    # i.e. the last query attends to the full kv (prefill-with-history)
    q_abs0 = qi * block_q + (sk - sq)
    k_start = ki * block_k

    # skip kv blocks entirely above the diagonal
    should_run = True
    if causal:
        should_run = k_start <= q_abs0 + block_q - 1

    @pl.when(should_run)
    def _run():
        # operands enter the MXU in their own dtype, accumulating in f32
        q = q_ref[0, 0]                               # [bq, hd]
        k = k_ref[0, 0]                               # [bk, hd]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_abs0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
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
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: [B,Sq,H,hd]; k,v: [B,Sk,KVH,hd] -> [B,Sq,H,hd]."""
    B, Sq, H, hd = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    grid = (B, H, Sq // block_q, Sk // block_k)

    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, sq=Sq, sk=Sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            # m, l: [block_q, 1]; acc: [block_q, hd] — all VMEM-resident
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
