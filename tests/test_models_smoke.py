"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step + one decode step on CPU, asserting shapes + finiteness.
(tests/test_lowering.py compiles every architecture's steps on a
multi-device mesh; tests/test_chip_compile.py compiles qwen2-0.5b at its
published widths for a described v5e.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs
from repro.models import registry
from tests.conftest import reduce_cfg

ARCHS = list_configs()


def make_batch(cfg, B=2, S=16):
    batch = {
        "tokens": jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) % cfg.vocab_size,
        "labels": jnp.ones((B, S), jnp.int32),
    }
    if cfg.is_encoder_decoder:
        batch["encoder_embeds"] = jnp.ones((B, cfg.encoder_frames, cfg.d_model),
                                           jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_train_step(self, arch, rng):
        cfg = reduce_cfg(get_config(arch))
        params = registry.init_params(cfg, rng)
        batch = make_batch(cfg)
        (loss, aux), grads = jax.jit(
            jax.value_and_grad(lambda p, b: registry.loss_fn(p, cfg, b),
                               has_aux=True))(params, batch)
        assert np.isfinite(float(loss)), arch
        gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
        assert np.isfinite(gn) and gn > 0, arch

    def test_forward_shapes(self, arch, rng):
        cfg = reduce_cfg(get_config(arch))
        params = registry.init_params(cfg, rng)
        batch = make_batch(cfg, B=2, S=16)
        logits, aux = jax.jit(lambda p, b: registry.forward(p, cfg, b))(params, batch)
        assert logits.shape == (2, 16, cfg.vocab_size), (arch, logits.shape)
        assert np.all(np.isfinite(np.asarray(logits, np.float32))), arch

    def test_decode_step(self, arch, rng):
        cfg = reduce_cfg(get_config(arch))
        params = registry.init_params(cfg, rng)
        B, S = 2, 32
        cache = registry.init_cache(cfg, B, S)
        toks = jnp.ones((B, 1), jnp.int32)
        pos = jnp.zeros((B,), jnp.int32)
        step = jax.jit(lambda p, c, t, q: registry.decode_step(p, cfg, c, t, q))
        logits, cache = step(params, cache, toks, pos)
        assert logits.shape == (B, 1, cfg.vocab_size), arch
        assert np.all(np.isfinite(np.asarray(logits, np.float32))), arch
        # second token with updated positions
        logits2, cache = step(params, cache, toks, pos + 1)
        assert np.all(np.isfinite(np.asarray(logits2, np.float32))), arch


class TestDecodePrefillConsistency:
    """Token-by-token decode must reproduce the parallel forward."""

    @pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-1.3b", "zamba2-1.2b"])
    def test_logits_match(self, arch, rng):
        cfg = reduce_cfg(get_config(arch))
        params = registry.init_params(cfg, rng)
        B, S = 1, 8
        toks = (jnp.arange(S, dtype=jnp.int32) * 7 % cfg.vocab_size)[None]
        batch = {"tokens": toks}
        full_logits, _ = registry.forward(params, cfg, batch)

        cache = registry.init_cache(cfg, B, 16)
        step = jax.jit(lambda p, c, t, q: registry.decode_step(p, cfg, c, t, q))
        got = []
        for t in range(S):
            logits, cache = step(params, cache, toks[:, t:t + 1],
                                 jnp.full((B,), t, jnp.int32))
            got.append(np.asarray(logits[:, 0], np.float32))
        got = np.stack(got, axis=1)
        np.testing.assert_allclose(
            got, np.asarray(full_logits, np.float32), atol=5e-2, rtol=5e-2)


class TestParamCounts:
    """Full configs must land near the published sizes."""

    EXPECTED_B = {
        "qwen2-0.5b": (0.40, 0.60), "qwen2.5-3b": (2.8, 3.4),
        "smollm-360m": (0.30, 0.42), "llama3-405b": (390, 420),
        "granite-moe-3b-a800m": (3.0, 3.6), "grok-1-314b": (300, 330),
        "zamba2-1.2b": (1.0, 1.4), "whisper-tiny": (0.03, 0.08),
        "pixtral-12b": (11.5, 13.0), "mamba2-1.3b": (1.2, 1.45),
    }

    @pytest.mark.parametrize("arch", ARCHS)
    def test_param_count(self, arch):
        cfg = get_config(arch)
        n = registry.param_count(cfg) / 1e9
        lo, hi = self.EXPECTED_B[arch]
        assert lo <= n <= hi, f"{arch}: {n:.3f}B not in [{lo},{hi}]"

    def test_moe_active_counts(self):
        g = get_config("granite-moe-3b-a800m")
        active = registry.param_count(g, active_only=True) / 1e9
        assert 0.7 <= active <= 1.0, active
        k = get_config("grok-1-314b")
        active = registry.param_count(k, active_only=True) / 1e9
        assert 70 <= active <= 95, active
