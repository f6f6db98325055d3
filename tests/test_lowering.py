"""Every architecture's step lowers and compiles under the sharding rules.

Each ``list_configs()`` architecture, reduced to a few narrow layers, is
built by ``build_cell`` for ``train``, ``prefill`` and ``decode`` on a
(4, 2) ("data", "model") mesh of host devices, compiled, and its HLO
analyzed.  The rules' "pod" branch (parameters, MoE token groups and KV
cache rows over ("pod", "data")) is compiled on a (2, 2, 2) ("pod",
"data", "model") mesh.  Each cell runs in a subprocess with 8 devices.
"""
import pytest

from repro.configs import list_configs
from tests._multidevice import run_with_devices


def _lower(arch: str, kind: str, mesh: tuple = (4, 2),
           axes: tuple = ("data", "model"), extra: str = "",
           pod_arg: int | None = None) -> str:
    return run_with_devices(f"""
        import jax
        from jax.sharding import NamedSharding
        from repro.configs import get_config
        from repro.configs.shapes import ShapeSpec
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_cell
        from repro.analysis import hlo
        from repro.models.transformer import VISION_PATCHES

        cfg = get_config("{arch}")
        kw = dict(num_layers=2, d_model=64, d_ff=128, vocab_size=256,
                  remat_policy="full")
        if cfg.num_heads:
            kw.update(num_heads=4, num_kv_heads=2, head_dim=16)
        if cfg.moe:
            kw["moe"] = cfg.moe.__class__(num_experts=4, top_k=2,
                                          expert_d_ff=64, group_size=64)
        if cfg.ssm:
            kw["ssm"] = cfg.ssm.__class__(d_state=16, expand=2, head_dim=16,
                                          chunk_size=16)
        if cfg.shared_attn_every:
            kw.update(num_layers=4, shared_attn_every=2, shared_attn_lora_rank=4)
        if cfg.is_encoder_decoder:
            kw.update(num_encoder_layers=2, encoder_frames=16,
                      max_position_embeddings=256)
        cfg = cfg.with_overrides(**kw)
        # a vision stub's image takes VISION_PATCHES of the positions
        seq = 64 + (VISION_PATCHES if cfg.frontend_stub == "vision" else 0)
        mesh = make_mesh({mesh!r}, {axes!r})
        shape = ShapeSpec("t", seq_len=seq, global_batch=8, kind="{kind}")
        cell = build_cell(cfg, shape, mesh{extra})
        pod_arg = {pod_arg!r}
        if pod_arg is not None:
            specs = [s.spec for s in jax.tree.leaves(
                cell.in_shardings[pod_arg],
                is_leaf=lambda x: isinstance(x, NamedSharding))]
            assert any("pod" in (a if isinstance(a, tuple) else (a,))
                       for s in specs for a in s if a), specs
        compiled = cell.lower().compile()
        mem = compiled.memory_analysis()
        res = hlo.analyze(compiled.as_text())
        assert res["flops"] > 0
        assert mem.temp_size_in_bytes >= 0
        print("LOWERING_OK", int(res["flops"]),
              round(res["collective_bytes_total"] / 1e3, 1))
    """)


@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
@pytest.mark.parametrize("arch", list_configs())
def test_mini_dryrun(arch, kind):
    out = _lower(arch, kind)
    assert "LOWERING_OK" in out


@pytest.mark.parametrize("arch,kind,pod_arg", [
    ("smollm-360m", "train", 0),             # params' "embed" over (pod, data)
    ("granite-moe-3b-a800m", "train", 0),    # and "moe_groups" in the layers
    ("qwen2-0.5b", "decode", 1),             # KV cache's "cache_batch"
])
def test_lowering_pod_mesh(arch, kind, pod_arg):
    out = _lower(arch, kind, mesh=(2, 2, 2), axes=("pod", "data", "model"),
                 pod_arg=pod_arg)
    assert "LOWERING_OK" in out


def test_mini_dryrun_with_optim_knobs():
    out = _lower("smollm-360m", "train",
                 extra=", cast_params_bf16=True, microbatches=2")
    assert "LOWERING_OK" in out
