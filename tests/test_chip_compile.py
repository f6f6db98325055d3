"""Compile the main path's Pallas kernels and the paged decode step for a
described TPU v5e chip, at published widths.

Nothing runs: the TPU compiler, which ships with jax here, compiles for a
chip that is described and not attached.  A compile that passes is not a
chip run, but it catches what interpret mode cannot — block shapes the
chip's tiling refuses, more VMEM than a kernel may use, a step that does
not fit the device.  The topology is described inside a fixture, so the
TPU library is loaded only by the process that runs these tests.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm_bwd, rmsnorm_fwd
from repro.kernels.ssd_scan import ssd_chunk
from repro.models import registry


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the cache but cannot be
    # read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, sharding, *shapes):
    compiled = jax.jit(fn).lower(*_on(sharding, shapes)).compile()
    return compiled.as_text()


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _compile_paged_step(cfg, sharding, lanes, max_blocks, bs, chunk=1):
    """The serve engine's unsharded serving program, compiled for
    ``lanes`` lanes of ``max_blocks`` blocks of ``bs`` positions over a
    pool of ``lanes * max_blocks + 1`` blocks: the decode step's
    ``[lanes, 1]`` tokens and fed mask, or with ``chunk`` > 1 a prefill
    chunk's ``[lanes, chunk]`` tokens and fed counts."""
    fed = _sds((lanes,), jnp.bool_ if chunk == 1 else jnp.int32)
    params = jax.eval_shape(
        lambda: registry.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: registry.init_paged_cache(
        cfg, lanes, lanes * max_blocks + 1, bs))
    return jax.jit(
        lambda p, c, t, q, bt, fd: registry.decode_step_paged(
            p, cfg, c, t, q, bt, fd)
    ).lower(*_on(sharding, (
        params, cache, _sds((lanes, chunk), jnp.int32),
        _sds((lanes,), jnp.int32), _sds((lanes, max_blocks), jnp.int32),
        fed))
    ).compile()


def _loop_bodies(hlo: str) -> list[str]:
    """The text of every while loop's body computation in ``hlo``."""
    bodies = []
    for name in set(re.findall(r"\bbody=%([\w.\-]+)", hlo)):
        head = re.search(rf"^%{re.escape(name)} ", hlo, re.MULTILINE)
        bodies.append(hlo[head.start():hlo.index("\n}", head.start())])
    return bodies


QWEN = get_config("qwen2-0.5b")          # 14/2 heads of 64
MAMBA = get_config("mamba2-1.3b")


def test_flash_attention_compiles(one_chip):
    hd = QWEN.resolved_head_dim()
    hlo = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
                   one_chip, _sds((1, 2048, QWEN.num_heads, hd)),
                   _sds((1, 2048, QWEN.num_kv_heads, hd)),
                   _sds((1, 2048, QWEN.num_kv_heads, hd)))
    assert "tpu_custom_call" in hlo


def test_flash_decode_compiles(one_chip):
    hd = QWEN.resolved_head_dim()
    hlo = _compile(flash_decode, one_chip,
                   _sds((8, QWEN.num_heads, hd)),
                   _sds((8, 2048, QWEN.num_kv_heads, hd)),
                   _sds((8, 2048, QWEN.num_kv_heads, hd)),
                   _sds((8,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_ssd_chunk_compiles(one_chip):
    from repro.models.mamba import dims
    _, nh, hp, ds = dims(MAMBA)
    Q = MAMBA.ssm.chunk_size
    assert (Q, nh, hp, ds) == (256, 64, 64, 128)
    hlo = _compile(ssd_chunk, one_chip,
                   _sds((4, Q, nh, hp)), _sds((4, Q, ds)), _sds((4, Q, ds)),
                   _sds((4, Q, nh), jnp.float32), _sds((nh,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_rmsnorm_compiles(one_chip, which):
    D = QWEN.d_model
    x = _sds((4096, D))
    s = _sds((D,), jnp.float32)
    if which == "fwd":
        hlo = _compile(rmsnorm_fwd, one_chip, x, s)
    else:
        hlo = _compile(rmsnorm_bwd, one_chip, x, s, x)
    assert "tpu_custom_call" in hlo


def test_qwen2_paged_decode_step_compiles(one_chip):
    """The serve engine's unsharded decode program at published widths:
    8 lanes, a 2048-position lane capacity in blocks of 16."""
    cfg = QWEN
    assert (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == \
        (24, 896, 4864, 151936)
    compiled = _compile_paged_step(cfg, one_chip, 8, 128, 16)
    mem = compiled.memory_analysis()
    # weights (f32) + the KV pool + temporaries fit one v5e's 16 GB
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < 16e9


def _assert_pool_not_relaid_out(compiled, num_blocks, bs):
    """The pool enters with its blocks dimension major, and no loop body
    copies a pool slice."""
    pool_formats = compiled.input_formats[0][1]
    for name, fmt in pool_formats.items():
        assert fmt.layout.major_to_minor[-1] != 1, (name, fmt.layout)
    bodies = _loop_bodies(compiled.as_text())
    assert bodies
    pool_slice = re.compile(rf"\[[\d,]*\b{num_blocks},{bs}\b[\d,]*\]")
    copies = [line.strip() for body in bodies for line in body.splitlines()
              if re.search(r" copy(-start)?\(", line)
              and pool_slice.search(line.split(" copy")[0])]
    assert not copies, copies


def test_qwen2_paged_pool_is_not_relaid_out_per_layer(one_chip):
    """At the serving benchmark's shapes (64 lanes of 161 blocks of 16,
    a pool of 10,305 blocks) the pool enters with its blocks dimension
    major, and the layer loop copies no per-layer pool slice.  A pool
    stored as [.., KVH, hd] = [.., 2, 64] took a blocks-minor layout, and
    each layer relaid out its K and V slices four times."""
    lanes, max_blocks, bs = 64, 161, 16
    compiled = _compile_paged_step(QWEN, one_chip, lanes, max_blocks, bs)
    _assert_pool_not_relaid_out(compiled, lanes * max_blocks + 1, bs)


def test_qwen2_paged_prefill_chunk_keeps_the_pool_layout(one_chip):
    """The benchmark's prefill chunk, ``[64, 8]`` tokens through the same
    serving program, scatters eight positions per lane and attends over
    them: the pool keeps the decode step's blocks-major layout, and the
    layer loop still copies no pool slice."""
    lanes, max_blocks, bs = 64, 161, 16
    compiled = _compile_paged_step(QWEN, one_chip, lanes, max_blocks, bs,
                                   chunk=8)
    _assert_pool_not_relaid_out(compiled, lanes * max_blocks + 1, bs)
