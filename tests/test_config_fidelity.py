"""The assigned architecture configs must match the assignment table
EXACTLY (spec deliverable f: "write src/repro/configs/<id>.py with the
exact config above")."""
import pytest

from repro.configs.base import get_config

# (n_layers, d_model, n_heads, n_kv, d_ff, vocab) from the assignment
TABLE = {
    "granite-3-8b": (40, 4096, 32, 8, 12800, 49155),
    "deepseek-v2-236b": (60, 5120, 128, 128, None, 102400),
    "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
    "stablelm-12b": (40, 5120, 32, 8, 13824, 100352),
    # vocab from the published config.json (microsoft/Phi-3-medium-4k-instruct)
    "phi3-medium-14b": (40, 5120, 40, 10, 17920, 32064),
    "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
    "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
    "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
    "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
    "gemma3-1b": (26, 1152, 4, 1, 6912, 262144),
}


@pytest.mark.parametrize("arch", sorted(TABLE))
def test_config_matches_assignment(arch):
    L, d, H, KV, ff, V = TABLE[arch]
    cfg = get_config(arch)
    assert cfg.n_layers == L
    assert cfg.d_model == d
    assert cfg.n_heads == H
    assert cfg.n_kv_heads == KV
    if ff is not None:
        assert cfg.d_ff == ff
    assert cfg.vocab_size == V
    assert cfg.source, "every config must cite its source"


def test_moe_details():
    ds = get_config("deepseek-v2-236b")
    assert ds.moe.n_experts == 160 and ds.moe.top_k == 6
    assert ds.moe.d_ff_expert == 1536 and ds.moe.n_shared_experts == 2
    assert ds.mla.kv_lora_rank == 512
    ar = get_config("arctic-480b")
    assert ar.moe.n_experts == 128 and ar.moe.top_k == 2
    assert ar.moe.dense_residual


def test_special_structure():
    g = get_config("gemma3-1b")
    assert g.sliding_window == 512 and g.global_every == 6   # 5:1 pattern
    z = get_config("zamba2-1.2b")
    assert z.ssm.kind == "mamba2" and z.ssm.d_state == 64
    assert z.hybrid.shared_attn_period == 6
    x = get_config("xlstm-1.3b")
    assert x.ssm.kind == "xlstm" and x.ssm.xlstm_unit == 8
    q = get_config("qwen2-vl-7b")
    assert q.mrope and sum(q.mrope_sections) == q.resolved_head_dim // 2
    s = get_config("seamless-m4t-large-v2")
    assert s.is_encdec and s.n_enc_layers == 24
