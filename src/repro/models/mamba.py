"""Mamba2 (SSD — state-space duality) blocks and LM. [arXiv:2405.21060]

TPU adaptation notes (see DESIGN.md §6):

* The CUDA reference fuses z/x/B/C/dt into one ``in_proj`` and runs one
  grouped conv over ``[x;B;C]``.  We keep **separate projections and
  convs per component** so that the head dimension (``nh``) shards
  cleanly over the tensor axis — the fused layout would interleave
  differently-sharded components in one matrix.
* The SSD chunked algorithm is expressed as matmuls (MXU-friendly):
  intra-chunk "attention" term + inter-chunk recurrent state carried by
  ``lax.scan``.  ``repro.kernels.ssd_scan`` is the Pallas version of the
  intra-chunk block.
* Decode keeps an O(1) recurrent state ``h [B, nh, hp, ds]`` — this is
  what makes ``long_500k`` run where full attention cannot.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding import shard_hint


def dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nh = d_inner // s.head_dim
    return d_inner, nh, s.head_dim, s.d_state


# ---------------------------------------------------------------------------
# Parameter spec (one stacked block set)
# ---------------------------------------------------------------------------

def block_spec(cfg: ModelConfig, layers: int):
    D = cfg.d_model
    d_inner, nh, hp, ds = dims(cfg)
    W = cfg.ssm.conv_width
    NL = layers
    lax = ("layers",)
    return {
        "z_proj": L.PSpec((NL, D, nh, hp), lax + ("embed", "heads", "head_dim"), fan_in=D),
        "x_proj": L.PSpec((NL, D, nh, hp), lax + ("embed", "heads", "head_dim"), fan_in=D),
        "b_proj": L.PSpec((NL, D, ds), lax + ("embed", "state"), fan_in=D),
        "c_proj": L.PSpec((NL, D, ds), lax + ("embed", "state"), fan_in=D),
        "dt_proj": L.PSpec((NL, D, nh), lax + ("embed", "heads"), fan_in=D),
        "conv_x": L.PSpec((NL, W, nh, hp), lax + ("conv", "heads", "head_dim"), fan_in=W),
        "conv_b": L.PSpec((NL, W, ds), lax + ("conv", "state"), fan_in=W),
        "conv_c": L.PSpec((NL, W, ds), lax + ("conv", "state"), fan_in=W),
        "a_log": L.PSpec((NL, nh), lax + ("heads",), init="ssm_a"),
        "d_skip": L.PSpec((NL, nh), lax + ("heads",), init="ones"),
        "dt_bias": L.PSpec((NL, nh), lax + ("heads",), init="ssm_dt"),
        "norm": L.PSpec((NL, nh, hp), lax + ("heads", "head_dim"), init="ones"),
        "out_proj": L.PSpec((NL, nh, hp, D), lax + ("heads", "head_dim", "embed"), fan_in=d_inner),
    }


def param_spec(cfg: ModelConfig):
    D, V = cfg.d_model, cfg.vocab_size
    spec = {
        "embed": L.PSpec((V, D), ("vocab", "embed"), init="embed"),
        "blocks": block_spec(cfg, cfg.num_layers),
        "block_norms": L.PSpec((cfg.num_layers, D), ("layers", "embed_nofsdp"), init="ones"),
        "final_norm": L.PSpec((D,), ("embed_nofsdp",), init="ones"),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = L.PSpec((D, V), ("embed", "vocab"), fan_in=D)
    return spec


def init_params(cfg, rng):
    return L.init_tree(param_spec(cfg), rng, jnp.dtype(cfg.param_dtype))


def param_axes(cfg):
    return L.axes_tree(param_spec(cfg))


def param_shapes(cfg):
    return L.shapes_tree(param_spec(cfg), jnp.dtype(cfg.param_dtype))


# ---------------------------------------------------------------------------
# Causal conv1d helpers
# ---------------------------------------------------------------------------

def _causal_conv(u, w):
    """u: [B, S, ...feat], w: [W, ...feat] — depthwise causal conv."""
    W = w.shape[0]
    feat = u.shape[2:]
    pad = jnp.zeros((u.shape[0], W - 1) + feat, u.dtype)
    up = jnp.concatenate([pad, u], axis=1)
    out = jnp.zeros_like(u)
    for i in range(W):
        out = out + up[:, i:i + u.shape[1]] * w[i].astype(u.dtype)
    return out


# ---------------------------------------------------------------------------
# SSD forward (chunked, matmul form)
# ---------------------------------------------------------------------------

def ssd_forward(xh, bm, cm, dt, a_log, *, chunk: int):
    """Chunked SSD. xh: [B,S,nh,hp]; bm/cm: [B,S,ds]; dt: [B,S,nh] (post-
    softplus). Returns y: [B,S,nh,hp].

    Within a chunk the quadratic "attention" form runs on the MXU; across
    chunks a recurrent state h [B,nh,hp,ds] is carried by lax.scan.
    """
    B, S, nh, hp = xh.shape
    ds = bm.shape[-1]
    Q = min(chunk, S)
    if S % Q != 0:
        Q = S
    nc = S // Q

    a = -jnp.exp(a_log.astype(jnp.float32))                 # [nh]
    dA = dt.astype(jnp.float32) * a                         # [B,S,nh]

    xc = xh.reshape(B, nc, Q, nh, hp)
    bc = bm.reshape(B, nc, Q, ds).astype(jnp.float32)
    cc = cm.reshape(B, nc, Q, ds).astype(jnp.float32)
    dtc = dt.reshape(B, nc, Q, nh).astype(jnp.float32)
    dAc = dA.reshape(B, nc, Q, nh)
    cum = jnp.cumsum(dAc, axis=2)                           # [B,nc,Q,nh]

    # intra-chunk: y[i] = sum_{j<=i} C_i.B_j exp(cum_i - cum_j) dt_j x_j
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # [B,nc,Q,Q,nh]
    seg = shard_hint(seg, "batch", None, None, None, "act_heads")
    tri = jnp.tril(jnp.ones((Q, Q), jnp.float32))
    Lmat = jnp.exp(jnp.clip(seg, -60.0, 0.0)) * tri[None, None, :, :, None]
    cb = jnp.einsum("bnis,bnjs->bnij", cc, bc,
                    preferred_element_type=jnp.float32)     # [B,nc,Q,Q]
    w = (cb[..., None] * Lmat).astype(xh.dtype)             # [B,nc,Q,Q,nh]
    w = shard_hint(w, "batch", None, None, None, "act_heads")
    xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(xh.dtype)
    y_intra = jnp.einsum("bnijh,bnjhp->bnihp", w, xdt,
                         preferred_element_type=jnp.float32)

    # chunk states: S_n = sum_j exp(cum_end - cum_j) dt_j x_j B_j^T
    decay_out = jnp.exp(jnp.clip(cum[:, :, -1:, :] - cum, -60.0, 0.0))  # [B,nc,Q,nh]
    sts = jnp.einsum("bnjhp,bnjh,bnjs->bnhps", xdt, decay_out, bc,
                     preferred_element_type=jnp.float32)    # [B,nc,nh,hp,ds]
    chunk_decay = jnp.exp(jnp.clip(jnp.sum(dAc, axis=2), -60.0, 0.0))  # [B,nc,nh]

    def scan_fn(h, inp):
        st, dec = inp                                       # [B,nh,hp,ds], [B,nh]
        h_prev = h                                          # state *entering* chunk
        h = h * dec[..., None, None] + st
        return h, h_prev

    h0 = jnp.zeros((B, nh, hp, ds), jnp.float32)
    sts_t = sts.transpose(1, 0, 2, 3, 4)                    # [nc,B,nh,hp,ds]
    dec_t = chunk_decay.transpose(1, 0, 2)                  # [nc,B,nh]
    _, h_prevs = jax.lax.scan(scan_fn, h0, (sts_t, dec_t))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)              # [B,nc,nh,hp,ds]

    # inter-chunk: y_inter[i] = exp(cum_i) * C_i . h_prev
    decay_in = jnp.exp(jnp.clip(cum, -60.0, 0.0))           # [B,nc,Q,nh]
    y_inter = jnp.einsum("bnis,bnhps,bnih->bnihp", cc, h_prevs, decay_in,
                         preferred_element_type=jnp.float32)
    y = (y_intra + y_inter).reshape(B, S, nh, hp)
    return y


def ssd_decode_step(h, x1, b1, c1, dt1, a_log):
    """One recurrent step. h: [B,nh,hp,ds]; x1: [B,nh,hp]; b1/c1: [B,ds];
    dt1: [B,nh] (post-softplus). Returns (y [B,nh,hp], h)."""
    a = -jnp.exp(a_log.astype(jnp.float32))
    dA = jnp.exp(dt1.astype(jnp.float32) * a)               # [B,nh]
    dBx = jnp.einsum("bhp,bs,bh->bhps", x1.astype(jnp.float32),
                     b1.astype(jnp.float32), dt1.astype(jnp.float32))
    h = h * dA[..., None, None] + dBx
    y = jnp.einsum("bhps,bs->bhp", h, c1.astype(jnp.float32))
    return y, h


# ---------------------------------------------------------------------------
# Full block (proj + conv + SSD + gate + out)
# ---------------------------------------------------------------------------

def block_forward(bp, cfg: ModelConfig, x):
    """x: [B,S,D] -> [B,S,D]."""
    dt_ = x.dtype
    d_inner, nh, hp, ds = dims(cfg)
    z = jnp.einsum("bsd,dhp->bshp", x, bp["z_proj"].astype(dt_))
    xh = jnp.einsum("bsd,dhp->bshp", x, bp["x_proj"].astype(dt_))
    bm = jnp.einsum("bsd,dk->bsk", x, bp["b_proj"].astype(dt_))
    cm = jnp.einsum("bsd,dk->bsk", x, bp["c_proj"].astype(dt_))
    dt_raw = jnp.einsum("bsd,dh->bsh", x, bp["dt_proj"].astype(dt_))

    xh = jax.nn.silu(_causal_conv(xh, bp["conv_x"]))
    bm = jax.nn.silu(_causal_conv(bm, bp["conv_b"]))
    cm = jax.nn.silu(_causal_conv(cm, bp["conv_c"]))
    xh = shard_hint(xh, "batch", "act_seq", "act_heads", "head_dim")

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                         bp["dt_bias"].astype(jnp.float32))
    y = ssd_forward(xh, bm, cm, dt, bp["a_log"], chunk=cfg.ssm.chunk_size)
    y = y + xh.astype(jnp.float32) * bp["d_skip"].astype(jnp.float32)[None, None, :, None]
    # gated RMSNorm (per head)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + cfg.rms_norm_eps) * bp["norm"].astype(jnp.float32)
    y = y.astype(dt_)
    out = jnp.einsum("bshp,hpd->bsd", y, bp["out_proj"].astype(dt_))
    return shard_hint(out, "batch", "act_seq", "act_embed")


def block_decode(bp, cfg: ModelConfig, state, x):
    """x: [B,1,D]; state: dict(conv_x, conv_b, conv_c, h). Returns (y, state)."""
    dt_ = x.dtype
    W = cfg.ssm.conv_width
    z = jnp.einsum("bsd,dhp->bshp", x, bp["z_proj"].astype(dt_))[:, 0]
    xh = jnp.einsum("bsd,dhp->bshp", x, bp["x_proj"].astype(dt_))[:, 0]
    bm = jnp.einsum("bsd,dk->bsk", x, bp["b_proj"].astype(dt_))[:, 0]
    cm = jnp.einsum("bsd,dk->bsk", x, bp["c_proj"].astype(dt_))[:, 0]
    dt_raw = jnp.einsum("bsd,dh->bsh", x, bp["dt_proj"].astype(dt_))[:, 0]

    def conv_step(cache, new, w):
        # cache: [B, W-1, ...feat]; new: [B, ...feat]
        seq = jnp.concatenate([cache, new[:, None]], axis=1)   # [B, W, feat]
        out = jnp.einsum("bw...,w...->b...", seq, w.astype(new.dtype))
        return jax.nn.silu(out), seq[:, 1:]

    xh, cx = conv_step(state["conv_x"], xh, bp["conv_x"])
    bm, cb = conv_step(state["conv_b"], bm, bp["conv_b"])
    cm, cc = conv_step(state["conv_c"], cm, bp["conv_c"])

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + bp["dt_bias"].astype(jnp.float32))
    y, h = ssd_decode_step(state["h"], xh, bm, cm, dt, bp["a_log"])
    y = y + xh.astype(jnp.float32) * bp["d_skip"].astype(jnp.float32)[None, :, None]
    y = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + cfg.rms_norm_eps) * bp["norm"].astype(jnp.float32)
    out = jnp.einsum("bhp,hpd->bd", y.astype(dt_), bp["out_proj"].astype(dt_))
    return out[:, None], {"conv_x": cx, "conv_b": cb, "conv_c": cc, "h": h}


def state_spec(cfg: ModelConfig, layers: int, batch: int):
    d_inner, nh, hp, ds = dims(cfg)
    W = cfg.ssm.conv_width
    NL = layers
    cdt = jnp.dtype(cfg.dtype)
    return {
        "conv_x": L.PSpec((NL, batch, W - 1, nh, hp),
                          ("layers", "cache_batch", None, "act_heads", "head_dim"),
                          init="zeros", dtype=cdt),
        "conv_b": L.PSpec((NL, batch, W - 1, ds),
                          ("layers", "cache_batch", None, "state"), init="zeros", dtype=cdt),
        "conv_c": L.PSpec((NL, batch, W - 1, ds),
                          ("layers", "cache_batch", None, "state"), init="zeros", dtype=cdt),
        "h": L.PSpec((NL, batch, nh, hp, ds),
                     ("layers", "cache_batch", "act_heads", "head_dim", "state"),
                     init="zeros", dtype=jnp.float32),
    }


# ---------------------------------------------------------------------------
# Full LM
# ---------------------------------------------------------------------------

def _remat(fn, cfg):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def forward(params, cfg: ModelConfig, tokens):
    from repro.models.transformer import embed_tokens, unembed
    x = embed_tokens(params, cfg, tokens)

    def body(x, scanned):
        bp, nrm = scanned
        h = L.rmsnorm(x, nrm, cfg.rms_norm_eps)
        return x + block_forward(bp, cfg, h), None

    x, _ = jax.lax.scan(_remat(body, cfg), x,
                        (params["blocks"], params["block_norms"]))
    x = L.rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return unembed(params, cfg, x), jnp.zeros((), jnp.float32)


def cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    # attention-free: cache is the recurrent state (O(1) in sequence)
    return state_spec(cfg, cfg.num_layers, batch)


def cache_shapes(cfg, batch, max_seq):
    return L.shapes_tree(cache_spec(cfg, batch, max_seq))


def cache_axes(cfg, batch, max_seq):
    return L.axes_tree(cache_spec(cfg, batch, max_seq))


def init_cache(cfg, batch, max_seq):
    return L.init_tree(cache_spec(cfg, batch, max_seq), jax.random.PRNGKey(0))


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, fed=None):
    from repro.models.transformer import unembed
    x, new_state = decode_hidden(params, cfg, cache, tokens, pos, fed)
    return unembed(params, cfg, x), new_state


def decode_hidden(params, cfg: ModelConfig, cache, tokens, pos, fed=None):
    """``fed`` [B] bool: lanes not fed a real token this call keep their
    recurrent state bit-frozen — the state is a running reduction, so a
    batched prefill of one lane must not advance the others (the paged
    path's ``masked_state`` discipline, ported to the slot path)."""
    from repro.models.transformer import embed_tokens
    x = embed_tokens(params, cfg, tokens)

    def body(x, scanned):
        bp, nrm, st = scanned
        h = L.rmsnorm(x, nrm, cfg.rms_norm_eps)
        y, new_st = block_decode(bp, cfg, st, h)
        if fed is not None:
            new_st = masked_state(fed, new_st, st)
        return x + y, new_st

    x, new_state = jax.lax.scan(
        body, x, (params["blocks"], params["block_norms"], cache))
    x = L.rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return x, new_state


def loss_fn(params, cfg: ModelConfig, batch):
    logits, aux = forward(params, cfg, batch["tokens"])
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(lse - gold)
    return nll + aux, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# Paged decode: O(1) recurrent state has no sequence blocks to page —
# the "paged" cache is simply per-lane state.  What the continuous-
# batching engine DOES need from an SSM family is fed-masking: the
# recurrent state is a running reduction, so a lane that is not fed a
# real token this call (idle, or mid-prefill of a different lane) must
# keep its state bit-frozen — attention caches survive garbage at the
# next write position because it is overwritten before the mask exposes
# it, but an SSM update is irreversible.
# ---------------------------------------------------------------------------

PAGED_HAS_BLOCKS = False    # O(1) state: no per-position pool blocks


def paged_cache_spec(cfg: ModelConfig, lanes: int, num_blocks: int,
                     block_size: int):
    return state_spec(cfg, cfg.num_layers, lanes)


def init_paged_cache(cfg: ModelConfig, lanes: int, num_blocks: int,
                     block_size: int):
    return L.init_tree(paged_cache_spec(cfg, lanes, num_blocks, block_size),
                       jax.random.PRNGKey(0))


def reset_paged_lane(cfg: ModelConfig, cache, lane_index: int):
    """Zero one lane's recurrent state (leaves are [NL, lanes, ...]):
    unlike KV blocks, state is never overwritten-before-read, so a
    recycled lane would otherwise leak its previous occupant's state."""
    return jax.tree.map(lambda a: a.at[:, lane_index].set(0), cache)


def reset_cache_lane(cfg: ModelConfig, cache, lane_index: int):
    """Slot-cache lane reset: the slot cache IS the state tree (leaves
    [NL, B, ...]), so a recycled slot must be zeroed exactly like a
    recycled paged lane."""
    return reset_paged_lane(cfg, cache, lane_index)


def masked_state(fed, new_state, old_state):
    """Per-lane select: advanced state where ``fed`` [B], frozen
    elsewhere."""
    def sel(new, old):
        m = fed.reshape((fed.shape[0],) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)
    return jax.tree.map(sel, new_state, old_state)


def decode_step_paged(params, cfg: ModelConfig, cache, tokens, pos, tables,
                      fed=None):
    from repro.models.transformer import unembed
    x, new_state = decode_hidden_paged(params, cfg, cache, tokens, pos,
                                       tables, fed)
    return unembed(params, cfg, x), new_state


def decode_hidden_paged(params, cfg: ModelConfig, cache, tokens, pos, tables,
                        fed=None):
    """tokens [B,T], pos [B], ``fed`` [B] leading columns fed (None =
    all): the transformer's paged contract.  T > 1 runs this one-token
    step over the columns (``scan_columns``)."""
    from repro.models.transformer import embed_tokens
    if tokens.shape[1] > 1:
        return scan_columns(
            lambda c, tok, p, f: decode_hidden_paged(params, cfg, c, tok, p,
                                                     tables, f),
            cache, tokens, pos, fed)
    live = L.fed_columns(fed, 1)
    x = embed_tokens(params, cfg, tokens)

    def body(x, scanned):
        bp, nrm, st = scanned
        h = L.rmsnorm(x, nrm, cfg.rms_norm_eps)
        y, new_st = block_decode(bp, cfg, st, h)
        if live is not None:
            new_st = masked_state(live[:, 0], new_st, st)
        return x + y, new_st

    x, new_state = jax.lax.scan(
        body, x, (params["blocks"], params["block_norms"], cache))
    x = L.rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return x, new_state


def scan_columns(step, cache, tokens, pos, fed):
    """T columns of ``tokens`` [B,T] through a one-token paged step, in
    one program, for families with recurrent state.  ``step(cache,
    tokens [B,1], pos [B], fed [B] bool) -> (hidden [B,1,D], cache)``
    runs once per column t, at positions pos + t, fed to the lanes given
    more than t columns: the arithmetic of T one-token calls.  Returns
    the hidden state [B,1,D] of each lane's last fed column, and the
    cache."""
    B, T = tokens.shape
    cols = L.fed_columns(fed, T)
    if cols is None:
        cols = jnp.ones((B, T), bool)

    def body(cache, column):
        tok, live, t = column
        x, cache = step(cache, tok[:, None], pos + t, live)
        return cache, x[:, 0]

    cache, xs = jax.lax.scan(body, cache, (tokens.T, cols.T, jnp.arange(T)))
    return L.last_fed(xs.transpose(1, 0, 2), fed), cache
