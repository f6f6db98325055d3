"""Reduced-width variants of the architectures, for CPU rehearsals.

``full`` is the published configuration unchanged; ``tiny`` and
``small`` shrink every width (and the family-specific blocks) so a CPU
can trace, compile and run the same code path in seconds.
"""
from __future__ import annotations

from repro.configs.base import ModelConfig, get_config

SCALES = {
    # ~1M params: fast CPU demo
    "tiny": dict(num_layers=2, d_model=64, d_ff=128, vocab_size=512,
                 num_heads=4, num_kv_heads=2, head_dim=16, remat_policy="none"),
    # ~25M params: slower but meaningful loss curves on CPU
    "small": dict(num_layers=4, d_model=256, d_ff=1024, vocab_size=4096,
                  num_heads=8, num_kv_heads=4, head_dim=32, remat_policy="none"),
    "full": {},
}


def scaled_config(arch: str, scale: str) -> ModelConfig:
    """``arch`` at ``scale`` (one of ``SCALES``)."""
    cfg = get_config(arch)
    overrides = dict(SCALES[scale])
    if not overrides:
        return cfg
    if cfg.moe:
        overrides["moe"] = cfg.moe.__class__(
            num_experts=4, top_k=2, expert_d_ff=overrides["d_ff"] // 2,
            group_size=64)
    if cfg.ssm:
        overrides["ssm"] = cfg.ssm.__class__(d_state=16, expand=2,
                                             head_dim=16, chunk_size=16)
    if cfg.shared_attn_every:
        overrides.update(num_layers=5, shared_attn_every=2,
                         shared_attn_lora_rank=8)
    if cfg.is_encoder_decoder:
        overrides.update(num_encoder_layers=2, encoder_frames=16,
                         max_position_embeddings=256)
    return cfg.with_overrides(**overrides)
