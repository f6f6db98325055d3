"""zamba2-style hybrid: Mamba2 backbone + ONE shared full-attention block
applied every ``shared_attn_every`` SSM layers, with per-site LoRA deltas
on its projections. [arXiv:2411.15242]

Scan layout: the 38 SSM layers are grouped as ``n_groups`` groups of
``shared_attn_every`` layers (remainder layers form a tail group without
an attention site), and the scan runs over groups.  Shared-attention
parameters are *broadcast* into the scan (same weights every site); only
the LoRA a/b factors are stacked per site — exactly zamba2's weight
sharing, and it keeps compile time depth-independent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba as M
from repro.sharding import shard_hint


def group_layout(cfg: ModelConfig) -> tuple[int, int, int]:
    """(n_groups, group_size, tail) for the scanned grouping."""
    k = cfg.shared_attn_every
    n_groups = cfg.num_layers // k
    tail = cfg.num_layers - n_groups * k
    return n_groups, k, tail


def param_spec(cfg: ModelConfig):
    D, V = cfg.d_model, cfg.vocab_size
    n_groups, k, tail = group_layout(cfg)
    n_sites = n_groups
    spec = {
        "embed": L.PSpec((V, D), ("vocab", "embed"), init="embed"),
        # grouped SSM blocks: [n_groups, k, ...] — scan over groups, inner
        # python loop over k (k is small and static)
        "blocks": M.block_spec(cfg, cfg.num_layers - tail),
        "block_norms": L.PSpec((cfg.num_layers - tail, D),
                               ("layers", "embed_nofsdp"), init="ones"),
        # the single shared attention+MLP block (no leading layer axis)
        "shared": {
            "attn": L.attn_spec(cfg),
            "mlp": L.mlp_spec(cfg),
            "ln1": L.PSpec((D,), ("embed_nofsdp",), init="ones"),
            "ln2": L.PSpec((D,), ("embed_nofsdp",), init="ones"),
        },
        # per-site LoRA on shared attn q/k/v (stacked on sites)
        "site_lora": _lora_spec(cfg, n_sites),
        "final_norm": L.PSpec((D,), ("embed_nofsdp",), init="ones"),
    }
    if tail:
        spec["tail_blocks"] = M.block_spec(cfg, tail)
        spec["tail_norms"] = L.PSpec((tail, D), ("layers", "embed_nofsdp"), init="ones")
    if not cfg.tie_embeddings:
        spec["lm_head"] = L.PSpec((D, V), ("embed", "vocab"), fan_in=D)
    return spec


def _lora_spec(cfg: ModelConfig, n_sites: int):
    D, H, KVH = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim()
    r = cfg.shared_attn_lora_rank
    spec = {}
    for nm, outd, outax in (("q", (H, hd), ("heads", "head_dim")),
                            ("k", (KVH, hd), ("kv_heads", "head_dim")),
                            ("v", (KVH, hd), ("kv_heads", "head_dim"))):
        spec[f"lora_{nm}_a"] = L.PSpec((n_sites, D, r), ("layers", "embed", None), fan_in=D)
        spec[f"lora_{nm}_b"] = L.PSpec((n_sites, r) + outd, ("layers", None) + outax, init="zeros")
    return spec


def init_params(cfg, rng):
    return L.init_tree(param_spec(cfg), rng, jnp.dtype(cfg.param_dtype))


def param_axes(cfg):
    return L.axes_tree(param_spec(cfg))


def param_shapes(cfg):
    return L.shapes_tree(param_spec(cfg), jnp.dtype(cfg.param_dtype))


def _remat(fn, cfg):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _shared_attn_fwd(cfg, sp, lora, x, positions, cache=None, pos=None):
    """Shared attention + MLP block with per-site LoRA merged in."""
    ap = dict(sp["attn"])
    ap.update(lora)
    h = L.rmsnorm(x, sp["ln1"], cfg.rms_norm_eps)
    q, k, v = L.attn_qkv(ap, h, positions, cfg)
    if cache is None:
        o = L.attention_dispatch(cfg, q, k, v, causal=True)
        new_cache = None
    else:
        kc, vc = cache
        B = x.shape[0]
        kc = kc.at[jnp.arange(B), pos].set(k[:, 0])
        vc = vc.at[jnp.arange(B), pos].set(v[:, 0])
        o = L.decode_attention(q, kc, vc, pos)
        new_cache = (kc, vc)
    x = x + L.attn_out(ap, o)
    h = L.rmsnorm(x, sp["ln2"], cfg.rms_norm_eps)
    x = x + L.mlp_apply(sp["mlp"], h)
    return shard_hint(x, "batch", "act_seq", "act_embed"), new_cache


def _stack_index(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def forward(params, cfg: ModelConfig, tokens):
    from repro.models.transformer import embed_tokens, unembed
    x = embed_tokens(params, cfg, tokens)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    n_groups, k, tail = group_layout(cfg)

    # reshape stacked blocks [n_groups*k, ...] -> [n_groups, k, ...]
    grouped = jax.tree.map(lambda a: a.reshape((n_groups, k) + a.shape[1:]),
                           params["blocks"])
    gnorms = params["block_norms"].reshape(n_groups, k, -1)

    def group_body(x, scanned):
        gblocks, gn, lora = scanned
        for i in range(k):
            bp = _stack_index(gblocks, i)
            h = L.rmsnorm(x, gn[i], cfg.rms_norm_eps)
            x = x + M.block_forward(bp, cfg, h)
        x, _ = _shared_attn_fwd(cfg, params["shared"], lora, x, positions)
        return x, None

    x, _ = jax.lax.scan(_remat(group_body, cfg), x,
                        (grouped, gnorms, params["site_lora"]))
    for i in range(tail):
        bp = _stack_index(params["tail_blocks"], i)
        h = L.rmsnorm(x, params["tail_norms"][i], cfg.rms_norm_eps)
        x = x + M.block_forward(bp, cfg, h)
    x = L.rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return unembed(params, cfg, x), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Cache + decode: SSM states for every mamba layer + KV cache per attn site
# ---------------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    n_groups, k, tail = group_layout(cfg)
    KVH, hd = cfg.num_kv_heads, cfg.resolved_head_dim()
    kv_shape = (n_groups, batch, max_seq, KVH, hd)
    kv_axes = ("layers", "cache_batch", "cache_seq", "act_kv_heads", "head_dim")
    spec = {
        "ssm": M.state_spec(cfg, cfg.num_layers - tail, batch),
        "attn_k": L.PSpec(kv_shape, kv_axes, init="zeros", dtype=jnp.dtype(cfg.dtype)),
        "attn_v": L.PSpec(kv_shape, kv_axes, init="zeros", dtype=jnp.dtype(cfg.dtype)),
    }
    if tail:
        spec["tail_ssm"] = M.state_spec(cfg, tail, batch)
    return spec


def cache_shapes(cfg, batch, max_seq):
    return L.shapes_tree(cache_spec(cfg, batch, max_seq))


def cache_axes(cfg, batch, max_seq):
    return L.axes_tree(cache_spec(cfg, batch, max_seq))


def init_cache(cfg, batch, max_seq):
    return L.init_tree(cache_spec(cfg, batch, max_seq), jax.random.PRNGKey(0))


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, fed=None):
    from repro.models.transformer import unembed
    x, new_cache = decode_hidden(params, cfg, cache, tokens, pos, fed)
    return unembed(params, cfg, x), new_cache


def decode_hidden(params, cfg: ModelConfig, cache, tokens, pos, fed=None):
    """``fed`` [B] bool freezes non-fed lanes' SSM state (see mamba.py)
    — the attention KV rows need no mask: a non-fed lane's write at its
    own ``pos`` is overwritten before the causal mask exposes it."""
    from repro.models.transformer import embed_tokens
    x = embed_tokens(params, cfg, tokens)
    n_groups, k, tail = group_layout(cfg)

    grouped = jax.tree.map(lambda a: a.reshape((n_groups, k) + a.shape[1:]),
                           params["blocks"])
    gnorms = params["block_norms"].reshape(n_groups, k, -1)
    gssm = jax.tree.map(lambda a: a.reshape((n_groups, k) + a.shape[1:]),
                        cache["ssm"])

    def group_body(x, scanned):
        gblocks, gn, lora, sts, kc, vc = scanned
        new_sts = []
        for i in range(k):
            bp = _stack_index(gblocks, i)
            st = _stack_index(sts, i)
            h = L.rmsnorm(x, gn[i], cfg.rms_norm_eps)
            y, new_st = M.block_decode(bp, cfg, st, h)
            if fed is not None:
                new_st = M.masked_state(fed, new_st, st)
            x = x + y
            new_sts.append(new_st)
        sts = jax.tree.map(lambda *a: jnp.stack(a), *new_sts)
        x, (kc, vc) = _shared_attn_fwd(cfg, params["shared"], lora, x,
                                       pos[:, None], cache=(kc, vc), pos=pos)
        return x, (sts, kc, vc)

    x, (new_ssm, new_k, new_v) = jax.lax.scan(
        group_body, x,
        (grouped, gnorms, params["site_lora"], gssm,
         cache["attn_k"], cache["attn_v"]))
    new_cache = {
        "ssm": jax.tree.map(lambda a: a.reshape((n_groups * k,) + a.shape[2:]), new_ssm),
        "attn_k": new_k, "attn_v": new_v,
    }
    if tail:
        tail_sts = []
        for i in range(tail):
            bp = _stack_index(params["tail_blocks"], i)
            st = _stack_index(cache["tail_ssm"], i)
            h = L.rmsnorm(x, params["tail_norms"][i], cfg.rms_norm_eps)
            y, new_st = M.block_decode(bp, cfg, st, h)
            if fed is not None:
                new_st = M.masked_state(fed, new_st, st)
            x = x + y
            tail_sts.append(new_st)
        new_cache["tail_ssm"] = jax.tree.map(lambda *a: jnp.stack(a), *tail_sts)
    x = L.rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return x, new_cache


def loss_fn(params, cfg: ModelConfig, batch):
    logits, aux = forward(params, cfg, batch["tokens"])
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(lse - gold)
    return nll + aux, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# Paged decode: block-table-indexed KV at each shared-attention site,
# fed-masked recurrent state for the SSM layers (see mamba.py notes)
# ---------------------------------------------------------------------------

PAGED_HAS_BLOCKS = True     # the attention sites cache KV per position


def paged_cache_spec(cfg: ModelConfig, lanes: int, num_blocks: int,
                     block_size: int):
    from repro.models.transformer import paged_pool_spec
    n_groups, k, tail = group_layout(cfg)
    kv = paged_pool_spec(cfg, n_groups, num_blocks, block_size,
                         cfg.resolved_head_dim(), jnp.dtype(cfg.dtype))
    spec = {
        "ssm": M.state_spec(cfg, cfg.num_layers - tail, lanes),
        "attn_k": kv,
        "attn_v": kv,
    }
    if tail:
        spec["tail_ssm"] = M.state_spec(cfg, tail, lanes)
    return spec


def init_paged_cache(cfg: ModelConfig, lanes: int, num_blocks: int,
                     block_size: int):
    return L.init_tree(paged_cache_spec(cfg, lanes, num_blocks, block_size),
                       jax.random.PRNGKey(0))


def reset_cache_lane(cfg: ModelConfig, cache, lane_index: int):
    """Slot-cache lane reset: zero the lane's SSM state (the ``ssm`` /
    ``tail_ssm`` subtrees are lane-indexed in both cache layouts, so the
    paged reset applies verbatim); attention rows are position-indexed
    and need no reset."""
    return reset_paged_lane(cfg, cache, lane_index)


def reset_paged_lane(cfg: ModelConfig, cache, lane_index: int):
    """Zero one lane's SSM state; the attention block pools need no
    reset (block discipline: stale bytes are never gathered unmasked)."""
    new = dict(cache)
    new["ssm"] = jax.tree.map(lambda a: a.at[:, lane_index].set(0),
                              cache["ssm"])
    if "tail_ssm" in cache:
        new["tail_ssm"] = jax.tree.map(lambda a: a.at[:, lane_index].set(0),
                                       cache["tail_ssm"])
    return new


def _shared_attn_paged(cfg, sp, lora, x, kc, vc, pos, tables, live):
    """Shared attention + MLP block against the paged KV pool of one
    site, one position per lane.  kc/vc: [num_blocks, bs, KVH*hd];
    tables: [B, max_blocks]; live: [B, 1] bool or None."""
    from repro.models.transformer import _paged_view, paged_scatter
    ap = dict(sp["attn"])
    ap.update(lora)
    h = L.rmsnorm(x, sp["ln1"], cfg.rms_norm_eps)
    q, k, v = L.attn_qkv(ap, h, pos[:, None], cfg)
    kc, vc = paged_scatter(kc, vc, k, v, tables, pos, live)
    o = L.decode_attention_merged(q, _paged_view(kc, tables),
                                  _paged_view(vc, tables), pos)
    x = x + L.attn_out(ap, o)
    h = L.rmsnorm(x, sp["ln2"], cfg.rms_norm_eps)
    x = x + L.mlp_apply(sp["mlp"], h)
    return shard_hint(x, "batch", "act_seq", "act_embed"), kc, vc


def decode_step_paged(params, cfg: ModelConfig, cache, tokens, pos, tables,
                      fed=None):
    from repro.models.transformer import unembed
    x, new_cache = decode_hidden_paged(params, cfg, cache, tokens, pos,
                                       tables, fed)
    return unembed(params, cfg, x), new_cache


def decode_hidden_paged(params, cfg: ModelConfig, cache, tokens, pos, tables,
                        fed=None):
    """The transformer's paged contract (tokens [B,T], pos [B], ``fed``
    [B] leading columns fed); T > 1 runs this one-token step over the
    columns (``mamba.scan_columns``), shared attention included."""
    from repro.models.transformer import embed_tokens
    if tokens.shape[1] > 1:
        return M.scan_columns(
            lambda c, tok, p, f: decode_hidden_paged(params, cfg, c, tok, p,
                                                     tables, f),
            cache, tokens, pos, fed)
    live = L.fed_columns(fed, 1)
    x = embed_tokens(params, cfg, tokens)
    n_groups, k, tail = group_layout(cfg)

    grouped = jax.tree.map(lambda a: a.reshape((n_groups, k) + a.shape[1:]),
                           params["blocks"])
    gnorms = params["block_norms"].reshape(n_groups, k, -1)
    gssm = jax.tree.map(lambda a: a.reshape((n_groups, k) + a.shape[1:]),
                        cache["ssm"])

    def group_body(x, scanned):
        gblocks, gn, lora, sts, kc, vc = scanned
        new_sts = []
        for i in range(k):
            bp = _stack_index(gblocks, i)
            st = _stack_index(sts, i)
            h = L.rmsnorm(x, gn[i], cfg.rms_norm_eps)
            y, new_st = M.block_decode(bp, cfg, st, h)
            if live is not None:
                new_st = M.masked_state(live[:, 0], new_st, st)
            x = x + y
            new_sts.append(new_st)
        sts = jax.tree.map(lambda *a: jnp.stack(a), *new_sts)
        x, kc, vc = _shared_attn_paged(cfg, params["shared"], lora, x,
                                       kc, vc, pos, tables, live)
        return x, (sts, kc, vc)

    x, (new_ssm, new_k, new_v) = jax.lax.scan(
        group_body, x,
        (grouped, gnorms, params["site_lora"], gssm,
         cache["attn_k"], cache["attn_v"]))
    new_cache = {
        "ssm": jax.tree.map(lambda a: a.reshape((n_groups * k,) + a.shape[2:]), new_ssm),
        "attn_k": new_k, "attn_v": new_v,
    }
    if tail:
        tail_sts = []
        for i in range(tail):
            bp = _stack_index(params["tail_blocks"], i)
            st = _stack_index(cache["tail_ssm"], i)
            h = L.rmsnorm(x, params["tail_norms"][i], cfg.rms_norm_eps)
            y, new_st = M.block_decode(bp, cfg, st, h)
            if live is not None:
                new_st = M.masked_state(live[:, 0], new_st, st)
            x = x + y
            tail_sts.append(new_st)
        new_cache["tail_ssm"] = jax.tree.map(lambda *a: jnp.stack(a), *tail_sts)
    x = L.rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return x, new_cache
