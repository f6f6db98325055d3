"""Jit'd public wrappers for the Pallas kernels.

Each op dispatches on ``impl``:

* ``"pallas"``    — the TPU kernel (use ``interpret=True`` on CPU).
* ``"xla"``       — the pure-jnp reference (also the backward path:
  forward runs the kernel, backward rematerializes through the
  reference formulation via ``jax.custom_vjp``).

The entry points compile for the TPU (Mosaic).  A CPU caller passes
``interpret=True`` explicitly; nothing here picks interpret mode on its
own, so a kernel that cannot compile fails instead of silently running
in the interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _fa_kernel
from repro.kernels.decode_attention import flash_decode as _fd_kernel
from repro.kernels.rmsnorm import rmsnorm_bwd as _rms_bwd_kernel
from repro.kernels.rmsnorm import rmsnorm_fwd as _rms_fwd_kernel
from repro.kernels.ssd_scan import ssd_chunk as _ssd_kernel


# ---------------------------------------------------------------------------
# flash attention (fwd kernel; bwd via reference remat)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, interpret: bool = False):
    return _fa_kernel(q, k, v, causal=causal, interpret=interpret)


def _fa_fwd(q, k, v, causal, interpret):
    return flash_attention(q, k, v, causal, interpret), (q, k, v)


def _fa_bwd(causal, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: ref.flash_attention_ref(
        q_, k_, v_, causal=causal), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# flash decode (inference only — no vjp needed)
# ---------------------------------------------------------------------------

def flash_decode(q, k_cache, v_cache, lengths, interpret: bool = False):
    return _fd_kernel(q, k_cache, v_cache, lengths, interpret=interpret)


# ---------------------------------------------------------------------------
# fused rmsnorm (fwd + bwd kernels)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rmsnorm(x, scale, eps: float = 1e-6, interpret: bool = False):
    shape = x.shape
    y = _rms_fwd_kernel(x.reshape(-1, shape[-1]), scale, eps, interpret=interpret)
    return y.reshape(shape)


def _rms_fwd(x, scale, eps, interpret):
    return rmsnorm(x, scale, eps, interpret), (x, scale)


def _rms_bwd(eps, interpret, res, g):
    x, scale = res
    shape = x.shape
    dx, ds = _rms_bwd_kernel(x.reshape(-1, shape[-1]), scale,
                             g.reshape(-1, shape[-1]), eps, interpret=interpret)
    return dx.reshape(shape), jnp.sum(ds, axis=0).astype(scale.dtype)


rmsnorm.defvjp(_rms_fwd, _rms_bwd)


# ---------------------------------------------------------------------------
# SSD intra-chunk (fwd kernel; bwd via reference remat)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_chunk(x, b, c, dt, a_log, interpret: bool = False):
    return _ssd_kernel(x, b, c, dt, a_log, interpret=interpret)


def _ssd_fwd(x, b, c, dt, a_log, interpret):
    return ssd_chunk(x, b, c, dt, a_log, interpret), (x, b, c, dt, a_log)


def _ssd_bwd(interpret, res, gs):
    x, b, c, dt, a_log = res
    _, vjp = jax.vjp(ref.ssd_chunk_ref, x, b, c, dt, a_log)
    return vjp(gs)


ssd_chunk.defvjp(_ssd_fwd, _ssd_bwd)
