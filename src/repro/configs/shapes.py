"""Step shapes + ShapeDtypeStruct ``input_specs`` builders.

A ``ShapeSpec`` names one step's sequence length, global batch and kind:
``train`` lowers ``train_step``, ``prefill`` lowers ``prefill_step`` and
``decode`` lowers ``serve_step`` (one new token against a KV cache of
``seq_len``).  ``launch/steps.build_cell`` builds the step for it.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    No device allocation: these are fed to ``jax.jit(...).lower()``.
    """
    B, S = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        S_text = S
        batch: dict[str, jax.ShapeDtypeStruct] = {}
        if cfg.frontend_stub == "vision":
            # one image (precomputed patch embeddings) per sequence; total
            # sequence length stays at the assigned S
            from repro.models.transformer import VISION_PATCHES
            S_text = S - VISION_PATCHES
            batch["vision_embeds"] = sds((B, VISION_PATCHES, cfg.d_model), jnp.bfloat16)
        batch["tokens"] = sds((B, S_text), jnp.int32)
        if cfg.is_encoder_decoder:
            batch["encoder_embeds"] = sds(
                (B, cfg.encoder_frames, cfg.d_model), jnp.bfloat16)
        if shape.kind == "train":
            batch["labels"] = sds((B, S_text), jnp.int32)
        return batch
    if shape.kind == "decode":
        # one new token against a cache of S (cache specs are built by the
        # model module itself; inputs are just the token + position)
        batch = {
            "tokens": sds((B, 1), jnp.int32),
            "pos": sds((B,), jnp.int32),
        }
        return batch
    raise ValueError(shape.kind)
