"""Uniform model API across families + analytical parameter counts."""
from __future__ import annotations

from types import ModuleType

import jax
import numpy as np

from repro.configs.base import ModelConfig


def module_for(cfg: ModelConfig) -> ModuleType:
    from repro.models import transformer, mamba, hybrid, encdec
    if cfg.family in ("dense", "moe", "vlm"):
        return transformer
    if cfg.family == "ssm":
        return mamba
    if cfg.family == "hybrid":
        return hybrid
    if cfg.family == "audio":
        return encdec
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Uniform entry points
# ---------------------------------------------------------------------------

def loss_fn(params, cfg, batch):
    from repro.models.layers import training_mode
    with training_mode():
        return module_for(cfg).loss_fn(params, cfg, batch)


def forward(params, cfg, batch):
    m = module_for(cfg)
    if cfg.is_encoder_decoder:
        return m.forward(params, cfg, batch["tokens"], batch["encoder_embeds"])
    if cfg.frontend_stub == "vision" and "vision_embeds" in batch:
        return m.forward(params, cfg, batch["tokens"],
                         vision_embeds=batch["vision_embeds"])
    return m.forward(params, cfg, batch["tokens"])


def decode_step(params, cfg, cache, tokens, pos, fed=None):
    """``fed`` [B] bool (optional): lanes not fed a real token this call
    — SSM families freeze their recurrent state (``masked_state``);
    attention-only families ignore it (their KV writes are safe)."""
    return module_for(cfg).decode_step(params, cfg, cache, tokens, pos, fed)


def decode_hidden(params, cfg, cache, tokens, pos, fed=None):
    """Decode up to the final norm (no unembed) — the split point for
    vocab-parallel serving.  Raises for families whose decode step does
    not factor this way (encoder-decoder has a bespoke unembed)."""
    m = module_for(cfg)
    if not hasattr(m, "decode_hidden"):
        raise NotImplementedError(
            f"decode_hidden not supported for family {cfg.family!r}")
    return m.decode_hidden(params, cfg, cache, tokens, pos, fed)


def reset_cache_lane(cfg, cache, lane_index):
    """Zero one lane's per-lane recurrent state in the SLOT cache (SSM
    families) — a recycled slot must not leak its previous occupant's
    state.  No-op for attention-only families (KV rows are
    position-indexed and overwritten before the causal mask exposes
    them)."""
    m = module_for(cfg)
    if hasattr(m, "reset_cache_lane"):
        return m.reset_cache_lane(cfg, cache, lane_index)
    return cache


def unembed_partial(params, cfg, x, vocab_start, vocab_len):
    """Vocab-parallel unembed slice (see transformer.unembed_partial)."""
    from repro.models import transformer
    return transformer.unembed_partial(params, cfg, x, vocab_start,
                                       vocab_len)


def init_params(cfg, rng):
    return module_for(cfg).init_params(cfg, rng)


def param_axes(cfg):
    return module_for(cfg).param_axes(cfg)


def param_shapes(cfg):
    return module_for(cfg).param_shapes(cfg)


def cache_shapes(cfg, batch, max_seq):
    return module_for(cfg).cache_shapes(cfg, batch, max_seq)


def cache_axes(cfg, batch, max_seq):
    return module_for(cfg).cache_axes(cfg, batch, max_seq)


def init_cache(cfg, batch, max_seq):
    return module_for(cfg).init_cache(cfg, batch, max_seq)


# ---------------------------------------------------------------------------
# Paged decode (block-table-indexed KV cache; see serve/kvcache.py)
# ---------------------------------------------------------------------------

def supports_paged(cfg: ModelConfig) -> bool:
    """True iff the family implements the paged decode entry points."""
    return hasattr(module_for(cfg), "decode_step_paged")


def paged_has_blocks(cfg: ModelConfig) -> bool:
    """True iff the paged cache actually pages KV by position (attention
    families).  SSM families keep lane-indexed recurrent state instead —
    the block allocator is bypassed but the lane/fed machinery applies."""
    return bool(getattr(module_for(cfg), "PAGED_HAS_BLOCKS", False))


def init_paged_cache(cfg, lanes, num_blocks, block_size):
    m = module_for(cfg)
    if not hasattr(m, "init_paged_cache"):
        raise NotImplementedError(
            f"paged decode not supported for family {cfg.family!r}")
    return m.init_paged_cache(cfg, lanes, num_blocks, block_size)


def decode_step_paged(params, cfg, cache, tokens, pos, tables, fed=None):
    """tokens [B,T] at positions pos[b] + t, tables [B,max_blocks],
    ``fed`` [B] the leading columns each lane is really fed (a bool is a
    count of 0 or 1; None = all) -> (logits [B,1,V] of each lane's last
    fed column, updated cache).  T = 1 is a decode step; the serve
    engine's prefill chunk is T = prefill_chunk."""
    return module_for(cfg).decode_step_paged(params, cfg, cache, tokens,
                                             pos, tables, fed)


def decode_hidden_paged(params, cfg, cache, tokens, pos, tables, fed=None):
    m = module_for(cfg)
    if not hasattr(m, "decode_hidden_paged"):
        raise NotImplementedError(
            f"decode_hidden_paged not supported for family {cfg.family!r}")
    return m.decode_hidden_paged(params, cfg, cache, tokens, pos, tables, fed)


def reset_paged_lane(cfg, cache, lane_index):
    return module_for(cfg).reset_paged_lane(cfg, cache, lane_index)


# ---------------------------------------------------------------------------
# Analytical parameter counts
# ---------------------------------------------------------------------------

def _spec_leaves_with_path(cfg):
    from repro.models import layers as L
    spec = module_for(cfg).param_spec(cfg)
    flat = jax.tree_util.tree_flatten_with_path(
        spec, is_leaf=lambda x: isinstance(x, L.PSpec))[0]
    return [("/".join(str(getattr(k, "key", k)) for k in path), leaf)
            for path, leaf in flat]


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    total = 0
    for path, leaf in _spec_leaves_with_path(cfg):
        n = int(np.prod(leaf.shape))
        if active_only and cfg.moe is not None and "/moe/" in f"/{path}/" \
                and "router" not in path:
            n = int(n * cfg.moe.top_k / cfg.moe.num_experts)
        total += n
    return total
