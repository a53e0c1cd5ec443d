"""Work counts against hand counts, and the peak table."""
import pytest

from harness import device, workcount as w

OPT_L2 = dict(n_layers=2, d_model=2048, n_heads=32, n_kv_heads=32,
              head_dim=64, d_ff=8192, vocab_size=50272, norm="layernorm",
              mlp="gelu")
PHI3_L1 = dict(n_layers=1, d_model=5120, n_heads=40, n_kv_heads=10,
               head_dim=128, d_ff=17920, vocab_size=32064, norm="rmsnorm",
               mlp="swiglu")


def test_parameter_counts():
    # 2 x (4 x 2048^2 + 2 x 2048 x 8192 + biases and norms) + 2 x 50272 x
    # 2048 + the final norm
    assert w.param_count(OPT_L2) == 306_618_368
    # 164.2 M embedding + 164.2 M head + 65.5 M attention + 275.3 M MLP
    assert w.param_count(PHI3_L1) == 669_137_920


def test_param_count_matches_the_program_layout():
    import dataclasses
    import jax
    from repro.configs.base import get_config
    from repro.models import model as M
    cfg = dataclasses.replace(get_config("opt-1.3b"), n_layers=2)
    assert M.count_params(cfg) == w.param_count(OPT_L2)


def test_train_flops_per_step():
    # 6 x 203.7 M matmul weights + 12 x 2 x 32 x 64 x 2048 per token,
    # 4096 tokens: ~5.4 TFLOP per 2-layer OPT-1.3B step
    per_token = w.train_flops_per_token(OPT_L2, 2048)
    assert w.matmul_params(OPT_L2) == 2 * 50_331_648 + 102_957_056
    assert per_token == 6 * 203_620_352 + 12 * 2 * 32 * 64 * 2048
    assert per_token * 4096 == pytest.approx(5.4165e12, rel=1e-4)


def test_decode_and_paged_attention_counts():
    full = dict(OPT_L2, n_layers=24)
    assert w.decode_flops(full, 100) == pytest.approx(
        2 * (24 * 50_331_648 + 102_957_056) + 4 * 24 * 32 * 64 * 100)
    # 2 slots at 10 and 30 keys: K and V of 40 positions plus q and out,
    # 32 x 64 floats each, per layer
    assert w.paged_attention_bytes(full, [10, 30]) == \
        24 * (40 * 2 * 32 * 64 * 4 + 2 * 2 * 32 * 64 * 4)
    assert w.paged_attention_flops(full, [10, 30]) == 24 * 4 * 40 * 32 * 64


def test_outer_step_work():
    flops, nbytes = w.outer_step_work(OPT_L2, rank=64, clusters=1)
    # 9 float32 passes over the round state: 11.04 GB
    assert nbytes == 4 * 306_618_368 * 9
    # embedding and head (50272 x 2048 each) alone: 2 x (6 m n r + 4 m r^2
    # + r^3); every leaf with both dims >= 64 adds its share
    emb = 6 * 50272 * 2048 * 64 + 4 * 50272 * 64 ** 2 + 64 ** 3
    assert flops > 2 * emb
    assert flops == pytest.approx(1.1919e11, rel=1e-3)
    two, _ = w.outer_step_work(OPT_L2, rank=64, clusters=2)
    assert two == 2 * flops


def test_peaks_of_a_v5e_and_refusal_of_the_unknown():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("TPU v4")
