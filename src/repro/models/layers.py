"""Shared model building blocks.

Conventions:

* Parameters live in nested dicts of ``jnp`` arrays.  The *structure* is
  declared once as a tree of :class:`PSpec` (shape + logical axes + init);
  ``init_tree`` / ``axes_tree`` / ``shapes_tree`` derive everything else,
  so lowering a step never has to materialize parameters.
* Layers that are scanned over carry a leading ``"layers"`` axis.
* Compute dtype is ``cfg.dtype`` (bf16 by default); softmax, norms and
  accumulations are f32.
* Attention here is the **XLA path**: a chunked online-softmax scan whose
  memory profile matches the Pallas flash kernel (``repro.kernels``), so
  the lowered HLO moves flash-attention-like bytes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.sharding import shard_hint

# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"        # normal | zeros | ones | embed | ssm_a | ssm_dt
    fan_in: int | None = None   # overrides fan-in for "normal"
    dtype: Any = None           # overrides param dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_pspec(x) -> bool:
    return isinstance(x, PSpec)


def init_tree(spec_tree, rng: jax.Array, param_dtype=jnp.float32):
    """Materialize a parameter tree from a PSpec tree."""
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=_is_pspec)
    rngs = jax.random.split(rng, len(leaves))
    out = []
    for spec, key in zip(leaves, rngs):
        dtype = spec.dtype or param_dtype
        if spec.init == "zeros":
            v = jnp.zeros(spec.shape, dtype)
        elif spec.init == "ones":
            v = jnp.ones(spec.shape, dtype)
        elif spec.init == "embed":
            v = (jax.random.normal(key, spec.shape, dtype) * 0.02).astype(dtype)
        elif spec.init == "ssm_a":
            # A_log init: log(uniform in [1, 16))
            lo, hi = 1.0, 16.0
            u = jax.random.uniform(key, spec.shape, jnp.float32, lo, hi)
            v = jnp.log(u).astype(dtype)
        elif spec.init == "ssm_dt":
            # dt_bias init: inverse softplus of uniform log-spaced dt
            lo, hi = 1e-3, 1e-1
            u = jax.random.uniform(key, spec.shape, jnp.float32)
            dt = jnp.exp(u * (math.log(hi) - math.log(lo)) + math.log(lo))
            v = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        elif spec.init == "normal":
            fan_in = spec.fan_in
            if fan_in is None:
                fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
            std = 1.0 / math.sqrt(max(fan_in, 1))
            v = (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)
        else:
            raise ValueError(spec.init)
        out.append(v)
    return jax.tree.unflatten(treedef, out)


def axes_tree(spec_tree):
    return jax.tree.map(lambda s: s.axes, spec_tree, is_leaf=_is_pspec)


def shapes_tree(spec_tree, param_dtype=jnp.float32):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or param_dtype),
        spec_tree, is_leaf=_is_pspec)


# ---------------------------------------------------------------------------
# Norms / positional embeddings / activations
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, scale, bias, eps: float):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotary embeddings. x: [..., S, H, hd]; positions: [..., S]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs[None, :]  # [..., S, half]
    cos = jnp.cos(angles)[..., :, None, :]   # [..., S, 1, half]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int) -> np.ndarray:
    pos = np.arange(length)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    return np.concatenate([np.sin(angle), np.cos(angle)], axis=-1).astype(np.float32)


def softcap(x, cap: float):
    if cap and cap > 0.0:
        return (jnp.tanh(x / cap) * cap).astype(x.dtype)
    return x


# ---------------------------------------------------------------------------
# Attention — chunked online-softmax (flash-equivalent XLA formulation)
# ---------------------------------------------------------------------------

NEG_INF = -1e30

# Training-mode context: some collective placements (the custom MoE block)
# only pay off when a backward pass follows.  registry.loss_fn sets this.
import contextlib as _contextlib
import threading as _threading

_mode = _threading.local()


@_contextlib.contextmanager
def training_mode():
    prev = getattr(_mode, "training", False)
    _mode.training = True
    try:
        yield
    finally:
        _mode.training = prev


def in_training() -> bool:
    return getattr(_mode, "training", False)


def attention(q, k, v, *, causal: bool, chunk: int = 1024, q_offset=0,
              logit_cap: float = 0.0, bias_mode: str | None = None):
    """Multi-head attention with GQA, scanned over KV chunks.

    q: [B, Sq, H, hd]; k, v: [B, Sk, KVH, hd].  Returns [B, Sq, H, hd].

    The KV sequence is processed in chunks with a running (max, denom,
    accumulator) — the same dataflow as the Pallas flash kernel, so the
    compiled HLO never materializes the [Sq, Sk] score matrix.
    """
    B, Sq, H, hd = q.shape
    _, Sk, KVH, _ = k.shape
    assert H % KVH == 0
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)

    chunk = min(chunk, Sk)
    if Sk % chunk != 0:
        chunk = Sk  # small/odd cases: single chunk
    n_chunks = Sk // chunk

    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    q_pos = q_offset + jnp.arange(Sq)

    # [n, B, chunk, KVH, hd]
    ks = k.reshape(B, n_chunks, chunk, KVH, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, n_chunks, chunk, KVH, hd).transpose(1, 0, 2, 3, 4)

    def step(carry, inputs):
        m, l, acc = carry
        idx, k_c, v_c = inputs
        # repeat KV group-wise to full heads; shardable over "act_heads"
        k_r = jnp.repeat(k_c, G, axis=2)   # [B, chunk, H, hd]
        v_r = jnp.repeat(v_c, G, axis=2)
        s = jnp.einsum("bqhd,bchd->bhqc", q, k_r,
                       preferred_element_type=jnp.float32)
        s = softcap(s, logit_cap) if logit_cap else s
        if causal:
            k_pos = idx * chunk + jnp.arange(chunk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_c = jnp.max(s, axis=-1)                       # [B,H,Sq]
        m_new = jnp.maximum(m, m_c)
        # guard fully-masked rows
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe[..., None])              # [B,H,Sq,chunk]
        corr = jnp.exp(jnp.minimum(m - m_new, 0.0))
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqc,bchd->bqhd", p.astype(v_r.dtype), v_r,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, H, hd), jnp.float32)
    if n_chunks == 1:
        (m, l, acc), _ = step((m0, l0, acc0), (jnp.int32(0), ks[0], vs[0]))
    else:
        (m, l, acc), _ = jax.lax.scan(
            step, (m0, l0, acc0), (jnp.arange(n_chunks), ks, vs))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def attention_blockskip(q, k, v, *, chunk: int = 1024, logit_cap: float = 0.0):
    """Causal attention over ONLY the lower-triangular (q-block, kv-block)
    pairs — a static schedule of nc(nc+1)/2 block GEMMs instead of nc²,
    halving attention FLOPs exactly (the flash-kernel block-skip, in XLA).
    """
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    chunk = min(chunk, S)
    if S % chunk or S == chunk:
        return attention(q, k, v, causal=True, chunk=chunk, logit_cap=logit_cap)
    nc = S // chunk
    scale = 1.0 / math.sqrt(hd)
    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)

    qr = qf.reshape(B, nc, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    kr = k.reshape(B, nc, chunk, KVH, hd).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(B, nc, chunk, KVH, hd).transpose(1, 0, 2, 3, 4)

    pairs = [(qi, ki) for qi in range(nc) for ki in range(qi + 1)]
    qi_arr = jnp.asarray([p[0] for p in pairs], jnp.int32)
    ki_arr = jnp.asarray([p[1] for p in pairs], jnp.int32)

    pos_in_chunk = jnp.arange(chunk)

    def step(carry, idx):
        m, l, acc = carry
        qi, ki = idx
        qb = jax.lax.dynamic_index_in_dim(qr, qi, 0, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(kr, ki, 0, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(vr, ki, 0, keepdims=False)
        k_r = jnp.repeat(kb, G, axis=2)
        v_r = jnp.repeat(vb, G, axis=2)
        s = jnp.einsum("bqhd,bchd->bhqc", qb, k_r,
                       preferred_element_type=jnp.float32)
        s = softcap(s, logit_cap) if logit_cap else s
        qpos = qi * chunk + pos_in_chunk
        kpos = ki * chunk + pos_in_chunk
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_q = jax.lax.dynamic_index_in_dim(m, qi, 0, keepdims=False)
        l_q = jax.lax.dynamic_index_in_dim(l, qi, 0, keepdims=False)
        a_q = jax.lax.dynamic_index_in_dim(acc, qi, 0, keepdims=False)
        m_c = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_q, m_c)
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(jnp.minimum(m_q - m_new, 0.0))
        l_new = l_q * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqc,bchd->bqhd", p.astype(v_r.dtype), v_r,
                        preferred_element_type=jnp.float32)
        a_new = a_q * corr.transpose(0, 2, 1)[..., None] + pv
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qi, 0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, qi, 0)
        return (m, l, acc), None

    m0 = jnp.full((nc, B, H, chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nc, B, H, chunk), jnp.float32)
    acc0 = jnp.zeros((nc, B, chunk, H, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (qi_arr, ki_arr))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 1, 3, 2)[..., None]
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)
    return out.astype(q.dtype)


ATTENTION_IMPLS = ("xla", "xla_blockskip", "ring")


def attention_dispatch(cfg, q, k, v, *, causal: bool = True):
    """Select the attention implementation from cfg.attention_impl.

    ``xla_blockskip`` and ``ring`` are causal schedules; non-causal
    attention runs the plain chunked ``xla`` path under either."""
    impl = cfg.attention_impl
    if impl not in ATTENTION_IMPLS:
        raise ValueError(f"attention_impl {impl!r} has no implementation; "
                         f"choose one of {ATTENTION_IMPLS}")
    if impl == "ring" and causal:
        from repro.collectives.ring_attention import ring_attention
        return ring_attention(q, k, v, causal=True, logit_cap=cfg.logit_softcap)
    if impl == "xla_blockskip" and causal:
        return attention_blockskip(q, k, v, chunk=cfg.attention_chunk,
                                   logit_cap=cfg.logit_softcap)
    return attention(q, k, v, causal=causal, chunk=cfg.attention_chunk,
                     logit_cap=cfg.logit_softcap)


def decode_attention(q, k_cache, v_cache, pos, *, logit_cap: float = 0.0):
    """Single-token attention against a (possibly sequence-sharded) cache.

    q: [B, 1, H, hd]; caches: [B, S, KVH, hd]; pos: [B] (#valid entries).
    GQA is computed via head grouping (no KV repeat), so the cache can be
    sharded on S or KVH and SPMD inserts the reduction collectives.
    """
    B, _, H, hd = q.shape
    _, S, KVH, _ = k_cache.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KVH, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32) * scale,
                   k_cache.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    s = softcap(s, logit_cap) if logit_cap else s
    valid = jnp.arange(S)[None, :] < pos[:, None] + 1       # [B,S]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", (p / l).astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def decode_attention_merged(q, k_view, v_view, pos, *,
                            logit_cap: float = 0.0):
    """``decode_attention`` for T query positions per lane against views
    whose KV heads and head_dim share one minor dimension, as the paged
    pool stores them.

    q: [B, T, H, hd], lane b's query t at position pos[b] + t; views:
    [B, S, KVH*hd]; pos: [B].  Query t of lane b sees keys
    s <= pos[b] + t.  Each KV head's T*G query rows contract with that
    head's columns of the view, so the view is read in its stored layout
    and never split into (KVH, hd); at T = 1 each head's products and
    sums are those of ``decode_attention``, so the two agree bit for bit.
    """
    B, T, H, hd = q.shape
    _, S, W = k_view.shape
    KVH = W // hd
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    # rows of one KV head ordered (t, g): row r is position pos + r // G
    qg = (q.reshape(B, T, KVH, G, hd).transpose(0, 2, 1, 3, 4)
          .reshape(B, KVH, T * G, hd).astype(jnp.float32) * scale)
    cols = [slice(h * hd, (h + 1) * hd) for h in range(KVH)]
    s = jnp.stack([jnp.einsum("bgd,bsd->bgs", qg[:, h],
                              k_view[:, :, c].astype(jnp.float32),
                              preferred_element_type=jnp.float32)
                   for h, c in enumerate(cols)], axis=1)   # [B,KVH,T*G,S]
    s = softcap(s, logit_cap) if logit_cap else s
    if T == 1:
        valid = (jnp.arange(S)[None, :] < pos[:, None] + 1)[:, None, :]
    else:
        last = pos[:, None] + jnp.arange(T * G)[None, :] // G   # [B,T*G]
        valid = jnp.arange(S)[None, None, :] <= last[:, :, None]
    s = jnp.where(valid[:, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = (p / l).astype(v_view.dtype)
    out = jnp.stack([jnp.einsum("bgs,bsd->bgd", p[:, h], v_view[:, :, c],
                                preferred_element_type=jnp.float32)
                     for h, c in enumerate(cols)], axis=1)  # [B,KVH,T*G,hd]
    out = out.reshape(B, KVH, T, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, hd).astype(q.dtype)


def fed_columns(fed, T: int):
    """[B, T] bool: which of a call's T columns each lane is fed, from
    ``fed`` [B], the number of leading columns fed (a bool is a count of
    0 or 1); ``None`` (every column fed) stays ``None``."""
    if fed is None:
        return None
    if T == 1 and fed.dtype == jnp.bool_:
        return fed[:, None]
    return jnp.arange(T)[None, :] < fed.astype(jnp.int32)[:, None]


def last_fed(x, fed):
    """Each lane's last fed column of x [B, T, ...] as [B, 1, ...]
    (column 0 for a lane fed nothing), for ``fed`` as ``fed_columns``
    takes it; x itself at T = 1."""
    B, T = x.shape[:2]
    if T == 1:
        return x
    n = jnp.full((B,), T) if fed is None else fed.astype(jnp.int32)
    return x[jnp.arange(B), jnp.clip(n - 1, 0, T - 1)][:, None]


# ---------------------------------------------------------------------------
# Attention block (params + apply), GQA + optional bias + RoPE
# ---------------------------------------------------------------------------

def padded_heads(cfg) -> tuple[int, int]:
    """(H, KVH) after optional padding to a multiple of cfg.pad_heads_to.

    CAVEAT: padding both H and KVH changes the GQA q→kv grouping, so this
    is an *architecture variant* for TP experiments, not an equivalence-
    preserving transform.  The semantics-
    preserving route to sharded attention for awkward head counts is ring
    attention (``attention_impl="ring"``), which shards the sequence.
    """
    H, KVH = cfg.num_heads, cfg.num_kv_heads
    p = cfg.pad_heads_to
    if not p or not H:
        return H, KVH
    pad = lambda n: ((n + p - 1) // p) * p
    return pad(H), pad(KVH)


def attn_spec(cfg, layers: int | None = None, lora_rank: int = 0):
    D = cfg.d_model
    H, KVH = padded_heads(cfg)
    hd = cfg.resolved_head_dim()
    L = (layers,) if layers is not None else ()
    lax = ("layers",) if layers is not None else ()
    spec = {
        "wq": PSpec(L + (D, H, hd), lax + ("embed", "heads", "head_dim"), fan_in=D),
        "wk": PSpec(L + (D, KVH, hd), lax + ("embed", "kv_heads", "head_dim"), fan_in=D),
        "wv": PSpec(L + (D, KVH, hd), lax + ("embed", "kv_heads", "head_dim"), fan_in=D),
        "wo": PSpec(L + (H, hd, D), lax + ("heads", "head_dim", "embed"), fan_in=H * hd),
    }
    if cfg.qkv_bias:
        spec["bq"] = PSpec(L + (H, hd), lax + ("heads", "head_dim"), init="zeros")
        spec["bk"] = PSpec(L + (KVH, hd), lax + ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = PSpec(L + (KVH, hd), lax + ("kv_heads", "head_dim"), init="zeros")
    if lora_rank:
        for nm, outd in (("q", (H, hd)), ("k", (KVH, hd)), ("v", (KVH, hd))):
            spec[f"lora_{nm}_a"] = PSpec(L + (D, lora_rank), lax + ("embed", None), fan_in=D)
            spec[f"lora_{nm}_b"] = PSpec(L + (lora_rank,) + outd, lax + (None,) + (("heads", "head_dim") if nm == "q" else ("kv_heads", "head_dim")), init="zeros")
    return spec


def attn_qkv(p, x, positions, cfg, *, use_rope=True):
    """Project to q, k, v (with optional bias/LoRA) and apply RoPE."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if "lora_q_a" in p:
        for nm, t in (("q", q), ("k", k), ("v", v)):
            a, b = p[f"lora_{nm}_a"].astype(dt), p[f"lora_{nm}_b"].astype(dt)
            delta = jnp.einsum("bsr,rhk->bshk", jnp.einsum("bsd,dr->bsr", x, a), b)
            if nm == "q":
                q = q + delta
            elif nm == "k":
                k = k + delta
            else:
                v = v + delta
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = shard_hint(q, "batch", "act_seq", "act_heads", "head_dim")
    k = shard_hint(k, "batch", "act_seq", "act_kv_heads", "head_dim")
    v = shard_hint(v, "batch", "act_seq", "act_kv_heads", "head_dim")
    return q, k, v


def attn_out(p, o):
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return shard_hint(y, "batch", "act_seq", "act_embed")


# ---------------------------------------------------------------------------
# MLP (SwiGLU) + MoE
# ---------------------------------------------------------------------------

def mlp_spec(cfg, layers: int | None = None, d_ff: int | None = None):
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    L = (layers,) if layers is not None else ()
    lax = ("layers",) if layers is not None else ()
    return {
        "wi_gate": PSpec(L + (D, F), lax + ("embed", "mlp"), fan_in=D),
        "wi_up": PSpec(L + (D, F), lax + ("embed", "mlp"), fan_in=D),
        "wo": PSpec(L + (F, D), lax + ("mlp", "embed"), fan_in=F),
    }


def mlp_apply(p, x, act=jax.nn.silu):
    dt = x.dtype
    g = jnp.einsum("bsd,df->bsf", x, p["wi_gate"].astype(dt))
    u = jnp.einsum("bsd,df->bsf", x, p["wi_up"].astype(dt))
    h = act(g) * u
    h = shard_hint(h, "batch", "act_seq", "act_mlp")
    return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(dt))


def moe_spec(cfg, layers: int | None = None):
    D, E = cfg.d_model, cfg.moe.num_experts
    F = cfg.moe.expert_d_ff
    L = (layers,) if layers is not None else ()
    lax = ("layers",) if layers is not None else ()
    return {
        "router": PSpec(L + (D, E), lax + ("embed", None), fan_in=D),
        "wi_gate": PSpec(L + (E, D, F), lax + ("experts", "embed", "expert_mlp"), fan_in=D),
        "wi_up": PSpec(L + (E, D, F), lax + ("experts", "embed", "expert_mlp"), fan_in=D),
        "wo": PSpec(L + (E, F, D), lax + ("experts", "expert_mlp", "embed"), fan_in=F),
    }


def _moe_expert_block(xg, dispatch, combine, wi_gate, wi_up, wo):
    """Dispatch → expert FFN → combine, with ALL model-axis collectives
    placed explicitly (paper thesis: user-level collective placement).

    Left to the partitioner, the model-axis partial-sums land on the
    *dispatched* tensors ([g,E,C,d]: top_k×capacity-inflated — measured
    8–12 GB/layer/device f32 on grok-1), and the group dim got gathered
    too.  Because dispatch and combine are linear in the token tensor,
    both reductions commute to TOKEN space: inside shard_map the only
    collectives are one fwd psum of y [g,t,d] and (via AD of the
    replicated inputs) psums of d_xg [g,t,d] + d_combine [g,t,E,C].
    ``dispatch`` must be stop_gradient-ed (it is a mask; its cotangent
    psum would be pure waste).

    Expert weights enter as their (F @ model)-sharded local blocks; the
    d-dim FSDP gather happens once per layer at the shard_map boundary.
    """
    from repro.sharding import resolve_spec
    mesh = compat.current_mesh()
    F = wi_gate.shape[-1]
    tp = 1 if mesh.empty else mesh.shape.get("model", 1)
    # The explicit block imposes expert-internal TP (F over `model`).
    # Worth it only for wide experts (grok: F/tp = 2048); for many-tiny-
    # expert MoEs (granite: F/tp = 32) the [g,t,E,C] combine-space psums
    # cost more than the narrow per-shard matmuls save, so fall back to
    # the capacity-sharded einsum formulation.
    if mesh.empty or tp == 1 or F % tp != 0 \
            or F // tp < 512 or not in_training():
        xe = jnp.einsum("gtec,gtd->gecd", dispatch, xg)
        xe = shard_hint(xe, "moe_groups", "act_experts", "expert_cap", "act_embed")
        h = (jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, wi_gate))
             * jnp.einsum("gecd,edf->gecf", xe, wi_up))
        h = shard_hint(h, "moe_groups", "act_experts", "expert_cap",
                       "act_expert_mlp")
        ye = jnp.einsum("gecf,efd->gecd", h, wo)
        return jnp.einsum("gtec,gecd->gtd", combine, ye)
    from jax.sharding import PartitionSpec as P
    g_spec = resolve_spec(("moe_groups",), (xg.shape[0],), mesh)
    gax = g_spec[0] if len(g_spec) else None

    batch_axes = (gax,) if isinstance(gax, str) else tuple(gax or ())
    blk = _make_moe_blk_vjp(batch_axes)
    return compat.shard_map(
        blk, mesh=mesh,
        in_specs=(P(gax, None, None), P(gax, None, None, None),
                  P(gax, None, None, None),
                  P(None, None, "model"), P(None, None, "model"),
                  P(None, "model", None)),
        out_specs=P(gax, None, None))(xg, dispatch, combine,
                                      wi_gate, wi_up, wo)


def _moe_blk_fwd_inner(xg_l, disp_l, comb_l, wg_l, wu_l, wo_l):
    xe = jnp.einsum("gtec,gtd->gecd", disp_l, xg_l)          # local
    g1 = jnp.einsum("gecd,edf->gecf", xe, wg_l)              # F-local
    u1 = jnp.einsum("gecd,edf->gecf", xe, wu_l)
    h = jax.nn.silu(g1) * u1
    ye_p = jnp.einsum("gecf,efd->gecd", h, wo_l)             # partial over F
    y_p = jnp.einsum("gtec,gecd->gtd", comb_l, ye_p)         # still partial
    return jax.lax.psum(y_p, "model"), (xe, g1, u1, ye_p)


import functools as _functools


@_functools.lru_cache(maxsize=None)
def _make_moe_blk_vjp(batch_axes: tuple):
    """custom_vjp MoE block for fixed batch (data) axes.

    Weight cotangents vary over the batch axes inside shard_map and must
    be psum'd over them explicitly (the FSDP gradient reduction — XLA's
    ReduceScatterCreator turns the AR+slice at the boundary into a
    reduce-scatter)."""

    @jax.custom_vjp
    def blk(xg_l, disp_l, comb_l, wg_l, wu_l, wo_l):
        return _moe_blk_fwd_inner(xg_l, disp_l, comb_l, wg_l, wu_l, wo_l)[0]

    def fwd(xg_l, disp_l, comb_l, wg_l, wu_l, wo_l):
        y = _moe_blk_fwd_inner(xg_l, disp_l, comb_l, wg_l, wu_l, wo_l)[0]
        return y, (xg_l, disp_l, comb_l, wg_l, wu_l, wo_l)

    def bwd(res, dy):
        # Hand-placed backward: the ONLY cross-model collectives are the
        # token-space psums of d_xg and d_comb — XLA's reassociation
        # otherwise moves them onto the capacity-inflated tensors.
        xg_l, disp_l, comb_l, wg_l, wu_l, wo_l = res
        # recompute forward intermediates locally (cheaper than saving)
        xe = jnp.einsum("gtec,gtd->gecd", disp_l, xg_l)
        g1 = jnp.einsum("gecd,edf->gecf", xe, wg_l)
        u1 = jnp.einsum("gecd,edf->gecf", xe, wu_l)
        sg = jax.nn.sigmoid(g1.astype(jnp.float32))
        silu_g = (g1.astype(jnp.float32) * sg).astype(g1.dtype)
        h = silu_g * u1
        ye_p = jnp.einsum("gecf,efd->gecd", h, wo_l)

        dy = dy.astype(xg_l.dtype)
        d_comb = jax.lax.psum(
            jnp.einsum("gtd,gecd->gtec", dy, ye_p), "model")
        d_ye = jnp.einsum("gtec,gtd->gecd", comb_l, dy)      # local
        d_h = jnp.einsum("gecd,efd->gecf", d_ye, wo_l)
        d_wo = jnp.einsum("gecf,gecd->efd", h, d_ye)
        d_silu_g = d_h * u1
        d_u1 = d_h * silu_g
        dsilu = (sg * (1 + g1.astype(jnp.float32) * (1 - sg))).astype(g1.dtype)
        d_g1 = d_silu_g * dsilu
        d_xe = (jnp.einsum("gecf,edf->gecd", d_g1, wg_l)
                + jnp.einsum("gecf,edf->gecd", d_u1, wu_l))  # local partial
        d_wg = jnp.einsum("gecd,gecf->edf", xe, d_g1)
        d_wu = jnp.einsum("gecd,gecf->edf", xe, d_u1)
        d_xg = jax.lax.psum(
            jnp.einsum("gtec,gecd->gtd", disp_l, d_xe), "model")
        if batch_axes:
            d_wg = jax.lax.psum(d_wg, batch_axes)
            d_wu = jax.lax.psum(d_wu, batch_axes)
            d_wo = jax.lax.psum(d_wo, batch_axes)
        return (d_xg, disp_l * 0, d_comb, d_wg, d_wu, d_wo)

    blk.defvjp(fwd, bwd)
    return blk


def _moe_route(p, x, cfg):
    """Router + GShard capacity dispatch, shared by every MoE apply path.

    Returns ``(xg, dispatch, combine, aux)`` — the grouped tokens
    ``[g, t, d]``, the (stop-gradient-ready) dispatch mask and combine
    weights ``[g, t, E, C]``, and the Switch aux loss.
    """
    mc = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, K = mc.num_experts, mc.top_k
    Gt = min(mc.group_size, T)
    if T % Gt != 0:
        Gt = T
    Gn = T // Gt
    C = max(1, int(math.ceil(Gt * K * mc.capacity_factor / E)))
    # round capacity to a multiple of 16 for clean "expert_cap" sharding
    C = int(min(Gt, ((C + 15) // 16) * 16))

    xg = x.reshape(Gn, Gt, D)
    xg = shard_hint(xg, "moe_groups", None, "act_embed")
    logits = jnp.einsum("gtd,de->gte", xg, p["router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)          # [g,t,K]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # GShard position-in-expert, one top-k slot at a time (k-major order) so
    # the peak live tensor stays [g,t,E,C] rather than [g,t,K,E,C].
    counts = jnp.zeros((Gn, 1, E), jnp.float32)             # tokens routed so far
    combine = jnp.zeros((Gn, Gt, E, C), x.dtype)
    sel_all = jnp.zeros((Gn, Gt, E), jnp.float32)
    for kk in range(K):
        sel_k = jax.nn.one_hot(gate_idx[:, :, kk], E, dtype=jnp.float32)
        pos_k = counts + jnp.cumsum(sel_k, axis=1) - sel_k  # [g,t,E]
        counts = counts + jnp.sum(sel_k, axis=1, keepdims=True)
        keep_k = (pos_k < C) * sel_k
        # scalar position of each token within its chosen expert
        pos_tok = jnp.sum(pos_k * sel_k, axis=-1)           # [g,t]
        cap_oh = jax.nn.one_hot(pos_tok, C, dtype=x.dtype)  # [g,t,C]
        w_k = (gate_vals[:, :, kk:kk + 1].astype(jnp.float32) * keep_k).astype(x.dtype)
        combine = combine + jnp.einsum("gte,gtc->gtec", w_k, cap_oh)
        sel_all = sel_all + sel_k
    combine = shard_hint(combine, "moe_groups", None, "act_experts", "expert_cap")
    dispatch = (combine > 0).astype(x.dtype)

    # load-balance aux loss (Switch-style)
    me = jnp.mean(probs, axis=(0, 1))                      # [E]
    fe = jnp.mean(sel_all, axis=(0, 1)) / K                # [E] fraction routed
    aux = E * jnp.sum(me * fe) * mc.aux_loss_weight
    return xg, dispatch, combine, aux


def moe_apply(p, x, cfg):
    """GShard-style grouped capacity dispatch (einsum formulation).

    Token groups are a batch-like dim sharded over (pod, data); the expert
    dim (or, when E is not divisible by the tensor axis, the capacity dim)
    shards over "model".  SPMD inserts the dispatch all-to-alls; for the
    explicitly placed expert-parallel variant (user-space Bruck
    all-to-alls on the progress engine) see
    :func:`moe_apply_expert_parallel`.  Returns (y, aux_loss).
    """
    B, S, D = x.shape
    xg, dispatch, combine, aux = _moe_route(p, x, cfg)

    y = _moe_expert_block(
        xg, jax.lax.stop_gradient(dispatch), combine,
        p["wi_gate"].astype(x.dtype), p["wi_up"].astype(x.dtype),
        p["wo"].astype(x.dtype))
    return y.reshape(B, S, D), aux


def moe_dispatch_alltoall(xe, mesh, axis: str, *, reverse: bool = False,
                          coll=None, spec=None, timeout: float = 120.0):
    """Block-transpose the dispatched tensor between the group-sharded
    and expert-sharded layouts — the MoE all-to-all, placed explicitly.

    ``xe`` is the global ``[G, E, C, d]`` dispatched tensor.  Forward
    (``reverse=False``): groups are sharded over ``axis``; the result is
    the same global array with the EXPERT dim sharded instead (each rank
    ends up holding every group's slice of its own experts).  Reverse
    undoes it (the combine-side all-to-all).  Both dims must divide the
    axis size.

    ``coll=None`` runs a jitted in-program ``lax.all_to_all``;  a
    :class:`~repro.collectives.nonblocking.UserCollectives` context runs
    the engine-driven Bruck ``ialltoall`` instead (paper §4.7).  All-to-
    all is pure data movement, so the two are bit-identical — the MoE
    twin of the fig-14 user-vs-native claim.
    """
    from jax.sharding import PartitionSpec as P
    n = dict(mesh.shape)[axis]
    G, E = xe.shape[0], xe.shape[1]
    if G % n or E % n:
        raise ValueError(
            f"moe_dispatch_alltoall: groups ({G}) and experts ({E}) must "
            f"divide the {axis!r} axis size ({n})")
    if n == 1:
        return xe
    if coll is None:
        if reverse:
            fn = compat.shard_map(
                lambda v: jax.lax.all_to_all(v, axis, 0, 1, tiled=True),
                mesh=mesh, in_specs=P(None, axis), out_specs=P(axis))
        else:
            fn = compat.shard_map(
                lambda v: jax.lax.all_to_all(v, axis, 1, 0, tiled=True),
                mesh=mesh, in_specs=P(axis), out_specs=P(None, axis))
        return jax.jit(fn)(xe)
    # user backend: ialltoall's payload is n*n stacked blocks (rank s's
    # rows are its n destination blocks); build block (s, r) = s's groups
    # x r's experts, transpose, and reassemble.
    Gl, El = G // n, E // n
    rest = xe.shape[2:]
    r_axes = tuple(range(4, 4 + len(rest)))
    if reverse:
        # expert-sharded in: rank i holds (source j, its El experts)
        pay = jnp.transpose(
            xe.reshape(n, Gl, n, El, *rest),
            (2, 0, 1, 3) + r_axes).reshape(n * n, Gl, El, *rest)
    else:
        pay = jnp.transpose(
            xe.reshape(n, Gl, n, El, *rest),
            (0, 2, 1, 3) + r_axes).reshape(n * n, Gl, El, *rest)
    out = coll.ialltoall(pay, mesh, axis, spec=spec).wait(timeout=timeout)
    out = out.reshape(n, n, Gl, El, *rest)
    if reverse:
        # row (j, i) = groups of j x experts of i -> group-major global
        return jnp.transpose(out, (0, 2, 1, 3) + r_axes).reshape(
            G, E, *rest)
    # row (i, j) = groups of j x experts of i -> group-major global
    return jnp.transpose(out, (1, 2, 0, 3) + r_axes).reshape(G, E, *rest)


@_functools.lru_cache(maxsize=None)
def _moe_expert_ffn_sharded(mesh, axis: str):
    """Jitted expert-sharded FFN: every contraction is expert-local, so
    the only collectives in the expert-parallel path are the two
    explicit all-to-alls around it."""
    from jax.sharding import PartitionSpec as P

    def ffn(xed, wg, wu, wo):
        h = (jax.nn.silu(jnp.einsum("gecd,edf->gecf", xed, wg))
             * jnp.einsum("gecd,edf->gecf", xed, wu))
        return jnp.einsum("gecf,efd->gecd", h, wo)

    return jax.jit(compat.shard_map(
        ffn, mesh=mesh,
        in_specs=(P(None, axis), P(axis), P(axis), P(axis)),
        out_specs=P(None, axis)))


def moe_apply_expert_parallel(p, x, cfg, mesh, axis: str = "model", *,
                              coll=None, spec=None, timeout: float = 120.0):
    """Expert-parallel MoE with EXPLICIT all-to-all placement — the
    dispatch path for many-tiny-expert configs (granite-moe-3b-a800m:
    E=40 experts of F=512, where expert-internal TP is a loss).

    Tokens are routed on the group-sharded layout, block-transposed to
    the expert shards (:func:`moe_dispatch_alltoall`), run through the
    expert-local FFN, and transposed back for the combine.  With
    ``coll`` the transposes are engine-driven user-space Bruck
    all-to-alls that overlap with host work; without, in-program native
    ones.  Either way the token math is identical einsums to
    :func:`moe_apply`'s fallback path, so outputs are bit-identical
    across all three paths.  Returns (y, aux_loss).
    """
    B, S, D = x.shape
    xg, dispatch, combine, aux = _moe_route(p, x, cfg)
    xe = jnp.einsum("gtec,gtd->gecd", dispatch, xg)      # [G, E, C, d]
    xed = moe_dispatch_alltoall(xe, mesh, axis, coll=coll, spec=spec,
                                timeout=timeout)
    ye = _moe_expert_ffn_sharded(mesh, axis)(
        xed, p["wi_gate"].astype(x.dtype), p["wi_up"].astype(x.dtype),
        p["wo"].astype(x.dtype))
    ye = moe_dispatch_alltoall(ye, mesh, axis, reverse=True, coll=coll,
                               spec=spec, timeout=timeout)
    y = jnp.einsum("gtec,gecd->gtd", combine, ye)
    return y.reshape(B, S, D), aux
