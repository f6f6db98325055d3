"""Decoder-only transformer LM (dense GQA / MoE / VLM backbone).

Covers: qwen2-0.5b, qwen2.5-3b, smollm-360m, llama3-405b,
granite-moe-3b-a800m, grok-1-314b, pixtral-12b.

Layers are stacked on a leading ``layers`` axis and executed with
``jax.lax.scan`` (keeps the HLO — and therefore compile time at 512
devices — independent of depth).  ``cfg.remat_policy`` wraps the scanned
body in ``jax.checkpoint``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding import shard_hint

VISION_PATCHES = 1024  # stub vision frontend: one 1024-patch image / seq


# ---------------------------------------------------------------------------
# Parameter spec
# ---------------------------------------------------------------------------

def param_spec(cfg: ModelConfig):
    D, V, NL = cfg.d_model, cfg.vocab_size, cfg.num_layers
    layer = {
        "attn": L.attn_spec(cfg, layers=NL),
        "ln1": L.PSpec((NL, D), ("layers", "embed_nofsdp"), init="ones"),
        "ln2": L.PSpec((NL, D), ("layers", "embed_nofsdp"), init="ones"),
    }
    if cfg.moe is not None:
        layer["moe"] = L.moe_spec(cfg, layers=NL)
    else:
        layer["mlp"] = L.mlp_spec(cfg, layers=NL)
    spec = {
        "embed": L.PSpec((V, D), ("vocab", "embed"), init="embed"),
        "layers": layer,
        "final_norm": L.PSpec((D,), ("embed_nofsdp",), init="ones"),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = L.PSpec((D, V), ("embed", "vocab"), fan_in=D)
    return spec


def init_params(cfg: ModelConfig, rng: jax.Array):
    return L.init_tree(param_spec(cfg), rng, jnp.dtype(cfg.param_dtype))


def param_axes(cfg: ModelConfig):
    return L.axes_tree(param_spec(cfg))


def param_shapes(cfg: ModelConfig):
    return L.shapes_tree(param_spec(cfg), jnp.dtype(cfg.param_dtype))


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _remat(fn, cfg: ModelConfig):
    if cfg.remat_policy in ("none", "subblock", "attn_only"):
        return fn          # sub-layer policies checkpoint inside the layer
    if cfg.remat_policy == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _layer_fwd(cfg: ModelConfig, x, lp, positions):
    if cfg.remat_policy == "subblock":
        return _layer_fwd_subblock(cfg, x, lp, positions)
    with jax.named_scope("attention"):
        h = L.rmsnorm(x, lp["ln1"], cfg.rms_norm_eps)
        q, k, v = L.attn_qkv(lp["attn"], h, positions, cfg)
        if cfg.remat_policy == "attn_only":
            # recompute ONLY the attention internals in backward:
            # everything else (projections, MLP) keeps its residuals —
            # removes the full forward recompute at ~3GB/device of extra
            # saved activations.
            attn_fn = jax.checkpoint(
                lambda q_, k_, v_: L.attention_dispatch(cfg, q_, k_, v_,
                                                        causal=True))
            o = attn_fn(q, k, v)
        else:
            o = L.attention_dispatch(cfg, q, k, v, causal=True)
        x = x + L.attn_out(lp["attn"], o)
    with jax.named_scope("mlp"):
        h = L.rmsnorm(x, lp["ln2"], cfg.rms_norm_eps)
        if cfg.moe is not None:
            y, aux = L.moe_apply(lp["moe"], h, cfg)
        else:
            y, aux = L.mlp_apply(lp["mlp"], h), jnp.zeros((), jnp.float32)
        x = x + y
    x = shard_hint(x, "batch", "act_seq", "act_embed")
    return x, aux


def _layer_fwd_subblock(cfg: ModelConfig, x, lp, positions):
    """Remat the projection/MLP sub-blocks but NOT the attention op, so a
    custom_vjp ring attention keeps its residuals and its forward ring is
    not replayed during backward (the whole-layer checkpoint would re-run
    it, doubling collective-permute traffic)."""
    def qkv_fn(x_, lp_):
        h = L.rmsnorm(x_, lp_["ln1"], cfg.rms_norm_eps)
        return L.attn_qkv(lp_["attn"], h, positions, cfg)

    q, k, v = jax.checkpoint(qkv_fn)(x, lp)
    o = L.attention_dispatch(cfg, q, k, v, causal=True)

    def rest_fn(x_, o_, lp_):
        x_ = x_ + L.attn_out(lp_["attn"], o_)
        h = L.rmsnorm(x_, lp_["ln2"], cfg.rms_norm_eps)
        if cfg.moe is not None:
            y, aux = L.moe_apply(lp_["moe"], h, cfg)
        else:
            y, aux = L.mlp_apply(lp_["mlp"], h), jnp.zeros((), jnp.float32)
        x_ = x_ + y
        return shard_hint(x_, "batch", "act_seq", "act_embed"), aux

    return jax.checkpoint(rest_fn)(x, o, lp)


def embed_tokens(params, cfg: ModelConfig, tokens, vision_embeds=None):
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(dt), x], axis=1)
    return shard_hint(x, "batch", "act_seq", "act_embed")


def forward_hidden(params, cfg: ModelConfig, tokens, vision_embeds=None):
    """tokens [B, S_text] -> (final normed hidden [B,S,D], aux_loss)."""
    x = embed_tokens(params, cfg, tokens, vision_embeds)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]

    body = _remat(lambda carry, lp: _scan_body(cfg, carry, lp, positions), cfg)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    return L.rmsnorm(x, params["final_norm"], cfg.rms_norm_eps), aux


def forward(params, cfg: ModelConfig, tokens, vision_embeds=None):
    """tokens [B, S_text] -> (logits [B, S, V], aux_loss)."""
    x, aux = forward_hidden(params, cfg, tokens, vision_embeds)
    return unembed(params, cfg, x), aux


def _scan_body(cfg, carry, lp, positions):
    x, aux = carry
    x, a = _layer_fwd(cfg, x, lp, positions)
    return (x, aux + a), None


def unembed(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    logits = L.softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    return shard_hint(logits, "batch", "act_seq", "act_vocab")


def unembed_partial(params, cfg: ModelConfig, x, vocab_start, vocab_len: int):
    """Vocab-parallel unembed: logits for ``vocab_len`` vocabulary rows
    starting at (traced) ``vocab_start`` — the tensor-parallel output
    projection.  Inside a model-axis ``shard_map`` each rank computes its
    slice and the full logits are the rank-order concatenation (gathered
    natively in-program, or by a user-space all-gather on the serve
    collective stream).  Softcap is elementwise, so slicing before it is
    exact."""
    if cfg.tie_embeddings:
        w = jax.lax.dynamic_slice_in_dim(params["embed"], vocab_start,
                                         vocab_len, axis=0)
        logits = jnp.einsum("bsd,vd->bsv", x, w.astype(x.dtype))
    else:
        w = jax.lax.dynamic_slice_in_dim(params["lm_head"], vocab_start,
                                         vocab_len, axis=1)
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    return L.softcap(logits.astype(jnp.float32), cfg.logit_softcap)


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    NL, KVH = cfg.num_layers, cfg.num_kv_heads
    hd = cfg.resolved_head_dim()
    axes = ("layers", "cache_batch", "cache_seq", "act_kv_heads", "head_dim")
    shape = (NL, batch, max_seq, KVH, hd)
    if cfg.kv_cache_dtype == "int8":
        s_axes = ("layers", "cache_batch", "cache_seq", "act_kv_heads", None)
        s_shape = (NL, batch, max_seq, KVH, 1)
        return {
            "k": L.PSpec(shape, axes, init="zeros", dtype=jnp.int8),
            "v": L.PSpec(shape, axes, init="zeros", dtype=jnp.int8),
            "k_scale": L.PSpec(s_shape, s_axes, init="zeros", dtype=jnp.float32),
            "v_scale": L.PSpec(s_shape, s_axes, init="zeros", dtype=jnp.float32),
        }
    return {
        "k": L.PSpec(shape, axes, init="zeros", dtype=jnp.dtype(cfg.dtype)),
        "v": L.PSpec(shape, axes, init="zeros", dtype=jnp.dtype(cfg.dtype)),
    }


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    return L.init_tree(cache_spec(cfg, batch, max_seq), jax.random.PRNGKey(0))


def cache_axes(cfg: ModelConfig, batch: int, max_seq: int):
    return L.axes_tree(cache_spec(cfg, batch, max_seq))


def cache_shapes(cfg: ModelConfig, batch: int, max_seq: int):
    return L.shapes_tree(cache_spec(cfg, batch, max_seq))


def _quantize_kv(t):
    """t: [..., KVH, hd] -> (int8 [..., KVH, hd], f32 scale
    [..., KVH, 1]): one scale per position and KV head."""
    tf = t.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(tf), axis=-1, keepdims=True) / 127.0,
                        1e-12)
    q = jnp.clip(jnp.round(tf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _layer_decode(cfg: ModelConfig, x, lp, kc, vc, pos, ks=None, vs=None):
    """One decoded token through one layer. x: [B,1,D]; kc/vc: [B,S,KVH,hd]
    (int8 with ks/vs scales when cfg.kv_cache_dtype == "int8")."""
    B = x.shape[0]
    h = L.rmsnorm(x, lp["ln1"], cfg.rms_norm_eps)
    q, k_new, v_new = L.attn_qkv(lp["attn"], h, pos[:, None], cfg)
    if ks is not None:
        kq, ksc = _quantize_kv(k_new[:, 0])
        vq, vsc = _quantize_kv(v_new[:, 0])
        kc = kc.at[jnp.arange(B), pos].set(kq)
        vc = vc.at[jnp.arange(B), pos].set(vq)
        ks = ks.at[jnp.arange(B), pos].set(ksc)
        vs = vs.at[jnp.arange(B), pos].set(vsc)
        # dequant fuses into the attention matmul: int8 bytes cross HBM
        k_use = (kc.astype(jnp.float32) * ks).astype(cfg.dtype)
        v_use = (vc.astype(jnp.float32) * vs).astype(cfg.dtype)
    else:
        kc = kc.at[jnp.arange(B), pos].set(k_new[:, 0])
        vc = vc.at[jnp.arange(B), pos].set(v_new[:, 0])
        k_use, v_use = kc, vc
    o = L.decode_attention(q, k_use, v_use, pos, logit_cap=cfg.logit_softcap)
    x = x + L.attn_out(lp["attn"], o)
    h = L.rmsnorm(x, lp["ln2"], cfg.rms_norm_eps)
    if cfg.moe is not None:
        y, _ = L.moe_apply(lp["moe"], h, cfg)
    else:
        y = L.mlp_apply(lp["mlp"], h)
    return x + y, kc, vc, ks, vs


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, fed=None):
    """tokens [B,1], pos [B] -> (logits [B,1,V], updated cache).

    ``fed`` is accepted for API uniformity with the SSM families and
    ignored: attention KV writes land at each lane's own ``pos`` and are
    overwritten before the causal mask can expose them, so a non-fed
    lane's cache row is already safe without masking."""
    x, new_cache = decode_hidden(params, cfg, cache, tokens, pos, fed)
    return unembed(params, cfg, x), new_cache


def decode_hidden(params, cfg: ModelConfig, cache, tokens, pos, fed=None):
    """Decode step up to (and including) the final norm: tokens [B,1],
    pos [B] -> (hidden [B,1,D], updated cache).  The unembed is split
    out so vocab-parallel serving can project per-rank slices.
    ``fed`` is ignored (see ``decode_step``)."""
    del fed
    x = embed_tokens(params, cfg, tokens)
    int8 = cfg.kv_cache_dtype == "int8"

    def body(x, scanned):
        if int8:
            lp, kc, vc, ks, vs = scanned
        else:
            lp, kc, vc = scanned
            ks = vs = None
        x, kc, vc, ks, vs = _layer_decode(cfg, x, lp, kc, vc, pos, ks, vs)
        return x, ((kc, vc, ks, vs) if int8 else (kc, vc))

    if int8:
        x, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"],
                      cache["k_scale"], cache["v_scale"]))
        new_cache = {"k": k_new, "v": v_new,
                     "k_scale": ks_new, "v_scale": vs_new}
    else:
        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = {"k": k_new, "v": v_new}
    x = L.rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return x, new_cache


# ---------------------------------------------------------------------------
# Paged KV cache + decode (block-table-indexed attention)
# ---------------------------------------------------------------------------
#
# The paged layout replaces the per-slot [B, max_seq] cache rows with a
# shared pool of fixed-size blocks [num_blocks, block_size]; each decode
# lane carries a block *table* [max_blocks] of physical pool indices.
# Per call, each lane's T new positions have their K/V scattered into
# (table[p//bs], p%bs) and attention runs over the table-gathered view
# [B, max_blocks*block_size, KVH*hd] — the same masked attention as the
# monolithic path, with positions past each query's own masked to exact
# zeros, so stale bytes in recycled blocks (and the shared scratch block
# 0 behind unallocated table entries) are unreachable.  A decode step is
# T = 1, and its logits are bit-identical to a monolithic cache row's; a
# prefill chunk is T = prefill_chunk positions of every mid-prefill lane
# in one call.
#
# Every block-pooled leaf is [layers, num_blocks, block_size, KVH*width]:
# heads and head_dim share one minor dimension (int8 scale pools have
# width 1).  A trailing (KVH, hd) pair such as (2, 64) pads 8x under the
# TPU's (8, 128) tiles, which leads the compiler to put the blocks
# dimension minor and relay out every layer's slice around the scatter;
# merged, the layout follows the shape and the blocks stay major.

PAGED_HAS_BLOCKS = True     # per-position KV: sequences occupy pool blocks


def paged_pool_spec(cfg: ModelConfig, layers: int, num_blocks: int,
                    block_size: int, width: int, dtype):
    """One block-pooled leaf: [layers, num_blocks, block_size, KVH*width]."""
    return L.PSpec((layers, num_blocks, block_size,
                    cfg.num_kv_heads * width),
                   ("layers", None, "cache_seq", "act_kv_heads"),
                   init="zeros", dtype=dtype)


def paged_cache_spec(cfg: ModelConfig, lanes: int, num_blocks: int,
                     block_size: int):
    NL, hd = cfg.num_layers, cfg.resolved_head_dim()
    if cfg.kv_cache_dtype == "int8":
        kv = paged_pool_spec(cfg, NL, num_blocks, block_size, hd, jnp.int8)
        sc = paged_pool_spec(cfg, NL, num_blocks, block_size, 1,
                             jnp.float32)
        return {"k": kv, "v": kv, "k_scale": sc, "v_scale": sc}
    kv = paged_pool_spec(cfg, NL, num_blocks, block_size, hd,
                         jnp.dtype(cfg.dtype))
    return {"k": kv, "v": kv}


def init_paged_cache(cfg: ModelConfig, lanes: int, num_blocks: int,
                     block_size: int):
    return L.init_tree(paged_cache_spec(cfg, lanes, num_blocks, block_size),
                       jax.random.PRNGKey(0))


def reset_paged_lane(cfg: ModelConfig, cache, lane_index: int):
    # nothing lane-indexed to clear: blocks are scatter-overwritten
    # before the masked attention can reach them
    return cache


def paged_scatter(kc, vc, k_new, v_new, tables, pos, live=None):
    """Scatter T positions' K/V [B, T, KVH, width] into the pools
    [num_blocks, bs, KVH*width]: lane b's column t at position
    pos[b] + t, i.e. (table[(pos+t)//bs], (pos+t)%bs).  ``live`` [B, T]
    bool (``layers.fed_columns``) sends the columns a lane is not fed to
    the scratch block 0, as idle lanes' whole tables already point there:
    an unfed column past the lane's capacity would otherwise land, by the
    gather's index clamp, in its last live block.  Block 0 is never
    gathered unmasked, so the duplicate writes there are harmless."""
    B, T = k_new.shape[:2]
    bs = kc.shape[1]
    at = pos[:, None] + jnp.arange(T)[None, :]                  # [B,T]
    phys = tables[jnp.arange(B)[:, None], at // bs]
    if live is not None:
        phys = jnp.where(live, phys, 0)
    phys, off = phys.reshape(-1), (at % bs).reshape(-1)
    return (kc.at[phys, off].set(k_new.reshape(B * T, -1)),
            vc.at[phys, off].set(v_new.reshape(B * T, -1)))


def _paged_view(pool, tables):
    """Gather [num_blocks, bs, X] through tables [B, max_blocks] into
    the per-lane contiguous view [B, max_blocks*bs, X]."""
    B, nb = tables.shape
    v = pool[tables]
    return v.reshape((B, nb * v.shape[2]) + v.shape[3:])


def _dequant_view(cfg: ModelConfig, view, scale):
    """int8 view [B, S, KVH*hd] times its scales [B, S, KVH], in the
    view's merged layout."""
    B, S, W = view.shape
    KVH = scale.shape[-1]
    full = view.reshape(B, S, KVH, W // KVH).astype(jnp.float32) \
        * scale[..., None]
    return full.reshape(B, S, W).astype(cfg.dtype)


def _layer_decode_paged(cfg: ModelConfig, x, lp, kc, vc, pos, tables, live,
                        ks=None, vs=None):
    """T positions per lane through one layer against the paged pool.
    x: [B,T,D]; kc/vc: [num_blocks, bs, KVH*hd] (int8 with ks/vs scale
    pools [num_blocks, bs, KVH] when cfg.kv_cache_dtype == "int8");
    tables: [B, max_blocks]; live: [B,T] bool or None.  The chunk's own
    keys are scattered before the view is gathered, so each position
    attends to the chunk's earlier positions and the cached prefix."""
    T = x.shape[1]
    with jax.named_scope("attention"):
        h = L.rmsnorm(x, lp["ln1"], cfg.rms_norm_eps)
        q, k_new, v_new = L.attn_qkv(lp["attn"], h,
                                     pos[:, None] + jnp.arange(T), cfg)
    with jax.named_scope("paged_view"):
        if ks is not None:
            kq, ksc = _quantize_kv(k_new)
            vq, vsc = _quantize_kv(v_new)
            kc, vc = paged_scatter(kc, vc, kq, vq, tables, pos, live)
            ks, vs = paged_scatter(ks, vs, ksc, vsc, tables, pos, live)
            k_use = _dequant_view(cfg, _paged_view(kc, tables),
                                  _paged_view(ks, tables))
            v_use = _dequant_view(cfg, _paged_view(vc, tables),
                                  _paged_view(vs, tables))
        else:
            kc, vc = paged_scatter(kc, vc, k_new, v_new, tables, pos, live)
            k_use = _paged_view(kc, tables)
            v_use = _paged_view(vc, tables)
    with jax.named_scope("attention"):
        o = L.decode_attention_merged(q, k_use, v_use, pos,
                                      logit_cap=cfg.logit_softcap)
        x = x + L.attn_out(lp["attn"], o)
    with jax.named_scope("mlp"):
        h = L.rmsnorm(x, lp["ln2"], cfg.rms_norm_eps)
        if cfg.moe is not None:
            y, _ = L.moe_apply(lp["moe"], h, cfg)
        else:
            y = L.mlp_apply(lp["mlp"], h)
    return x + y, kc, vc, ks, vs


def decode_step_paged(params, cfg: ModelConfig, cache, tokens, pos, tables,
                      fed=None):
    """tokens [B,T], pos [B], tables [B,max_blocks] -> (logits [B,1,V] of
    each lane's last fed column, updated pool cache).  See
    ``decode_hidden_paged`` for ``pos`` and ``fed``."""
    x, new_cache = decode_hidden_paged(params, cfg, cache, tokens, pos,
                                       tables, fed)
    with jax.named_scope("logits"):
        return unembed(params, cfg, x), new_cache


def decode_hidden_paged(params, cfg: ModelConfig, cache, tokens, pos, tables,
                        fed=None):
    """Paged step up to (and including) the final norm: lane b's T
    columns of ``tokens`` [B,T] are positions pos[b] .. pos[b]+T-1, and
    ``fed`` [B] (None = all) is how many leading columns the lane is
    really fed; T = 1 is one decode step.  Unfed columns write the
    scratch block and their outputs are dropped (at T = 1 an unfed
    lane's next-write position would be overwritten before the mask
    exposes it anyway).  Returns the hidden state [B,1,D] of each lane's
    last fed column, and the updated pool."""
    x = embed_tokens(params, cfg, tokens)
    live = L.fed_columns(fed, tokens.shape[1])
    int8 = cfg.kv_cache_dtype == "int8"

    def body(x, scanned):
        if int8:
            lp, kc, vc, ks, vs = scanned
        else:
            lp, kc, vc = scanned
            ks = vs = None
        x, kc, vc, ks, vs = _layer_decode_paged(cfg, x, lp, kc, vc, pos,
                                                tables, live, ks, vs)
        return x, ((kc, vc, ks, vs) if int8 else (kc, vc))

    if int8:
        x, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"],
                      cache["k_scale"], cache["v_scale"]))
        new_cache = {"k": k_new, "v": v_new,
                     "k_scale": ks_new, "v_scale": vs_new}
    else:
        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = {"k": k_new, "v": v_new}
    x = L.rmsnorm(L.last_fed(x, fed), params["final_norm"], cfg.rms_norm_eps)
    return x, new_cache


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def loss_fn(params, cfg: ModelConfig, batch):
    labels = batch["labels"]
    if cfg.loss_impl == "chunked_vocab" and not cfg.logit_softcap:
        from repro.train.losses import chunked_vocab_xent
        x, aux = forward_hidden(params, cfg, batch["tokens"],
                                vision_embeds=batch.get("vision_embeds"))
        if x.shape[1] != labels.shape[1]:        # VLM: loss on text positions
            x = x[:, -labels.shape[1]:]
        with jax.named_scope("logits"):
            if cfg.tie_embeddings:
                nll = chunked_vocab_xent(x, params["embed"], labels,
                                         cfg.loss_vocab_chunk, False)
            else:
                nll = chunked_vocab_xent(x, params["lm_head"], labels,
                                         cfg.loss_vocab_chunk, True)
        return nll + aux, {"nll": nll, "aux": aux}
    x, aux = forward_hidden(params, cfg, batch["tokens"],
                            vision_embeds=batch.get("vision_embeds"))
    with jax.named_scope("logits"):
        logits = unembed(params, cfg, x)
        if logits.shape[1] != labels.shape[1]:   # VLM: loss on text positions
            logits = logits[:, -labels.shape[1]:]
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = jnp.mean(lse - gold)
    return nll + aux, {"nll": nll, "aux": aux}
