"""The launchers' library surface: config scaling, the compile-cache
placement, attention-implementation validation, and one tiny end-to-end
run of each launcher that must report failure honestly."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.scales import scaled_config
from repro.launch import compile_cache
from repro.models import layers as L


def test_full_scale_is_the_published_config():
    assert scaled_config("qwen2-0.5b", "full") == get_config("qwen2-0.5b")
    tiny = scaled_config("mamba2-1.3b", "tiny")
    assert tiny.d_model == 64 and tiny.ssm.chunk_size == 16


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_defaults_to_a_fixed_repo_path(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path    # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("impl", ["pallas", "flash", ""])
def test_attention_dispatch_rejects_unimplemented(impl):
    cfg = scaled_config("qwen2-0.5b", "tiny").with_overrides(
        attention_impl=impl)
    q = jnp.zeros((1, 8, 4, 16))
    kv = jnp.zeros((1, 8, 2, 16))
    with pytest.raises(ValueError, match="no implementation"):
        L.attention_dispatch(cfg, q, kv, kv)


@pytest.mark.parametrize("impl", L.ATTENTION_IMPLS)
def test_attention_dispatch_runs_every_listed_impl(impl):
    cfg = scaled_config("qwen2-0.5b", "tiny").with_overrides(
        attention_impl=impl, attention_chunk=4)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 8, 4, 16))
    k = jax.random.normal(ks[1], (1, 8, 2, 16))
    v = jax.random.normal(ks[2], (1, 8, 2, 16))
    out = L.attention_dispatch(cfg, q, k, v)
    want = L.attention(q, k, v, causal=True, chunk=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_serve_launcher_serves_and_reports(capsys, monkeypatch):
    from repro.launch import serve
    # main() turns the persistent compile cache on; tests never do
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    rc = serve.main(["--scale", "tiny", "--requests", "4", "--slots", "2",
                     "--max-new", "3", "--prompt-len", "3", "9"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "served 4 requests, 12 tokens" in out


def test_serve_shortfalls_flag_failed_and_short_requests():
    from repro.launch import serve
    args = serve.parse_args(["--scale", "tiny", "--requests", "2",
                             "--slots", "2", "--max-new", "2"])
    server = serve.build(args)
    try:
        prompts = serve.random_prompts(server.cfg, 2, args.prompt_len)
        assert all(args.prompt_len[0] <= len(p) <= args.prompt_len[1]
                   for p in prompts)
        reqs = serve.serve(server, prompts, args.max_new)
        assert serve.shortfalls(server, reqs) == []
        reqs[0].out_tokens.pop()                       # fell short
        server.srv.decode_errors.append(RuntimeError("device lost"))
        problems = serve.shortfalls(server, reqs)
        assert len(problems) == 2
        assert any("device lost" in p for p in problems)
        assert any(p.startswith(f"{reqs[0].request_id}: 1/2") for p in problems)
    finally:
        serve.close(server)


def test_train_launcher_run_updates_every_leaf(tmp_path):
    from repro.launch import train
    args = train.parse_args(["--scale", "tiny", "--steps", "2",
                             "--global-batch", "4", "--seq", "16",
                             "--ckpt-dir", str(tmp_path)])
    result = train.run(args)
    assert [m["step"] for m in result.log] == [0, 1]
    assert all(np.isfinite(m["loss"]) for m in result.log)
    assert result.moved == result.leaves == len(jax.tree.leaves(result.params))
