"""The work counts by hand at the cells' shapes, and the peaks table."""
import json
from pathlib import Path

import pytest

import run as harness
from work import decode_gqa, model

BENCH = Path(__file__).resolve().parents[1]
CFG = json.loads((BENCH / "configs" / "qwen2.5-3b-precise.json").read_text())
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def test_matmul_params_by_hand():
    # per layer: q 2048x2048, k and v 2048x256, o 2048x2048, three
    # 2048x11008 MLP matrices; 36 layers, then the 2048x151936 head
    layer = 2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
    assert layer == 77_070_336
    assert model.matmul_params(CFG) == 36 * layer + 2048 * 151936
    assert model.matmul_params(CFG) == 3_085_697_024


def test_decode_flops_by_hand():
    kv = [100, 700]
    per_token = 2 * 3_085_697_024
    attn = [36 * 4 * 16 * 128 * k for k in kv]
    assert model.decode_flops(CFG, kv) == 2 * per_token + sum(attn)


def test_decode_gqa_counts_by_hand():
    ops, nbytes = decode_gqa.work(CFG, [512])
    assert ops == 4 * 16 * 128 * 512
    assert nbytes == 2 * 512 * 2 * 128 * 2 + 2 * 16 * 128 * 2
    t, by_bytes = decode_gqa.least_time(CFG, [512], PEAKS)
    assert by_bytes == 1.0
    assert t == pytest.approx(36 * nbytes / 819e9)


def test_decode_gqa_counts_with_an_explicit_head_dim():
    # Qwen3-4B's shape: 2560 / 32 heads would be 80, its config states 128
    cfg = {"model_type": "qwen2", "num_hidden_layers": 36,
           "hidden_size": 2560, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 128,
           "intermediate_size": 9728, "vocab_size": 151936}
    ops, nbytes = decode_gqa.work(cfg, [512])
    assert ops == 4 * 32 * 128 * 512
    assert nbytes == 2 * 512 * 8 * 128 * 2 + 2 * 32 * 128 * 2
    assert nbytes == 2_113_536
    t, by_bytes = decode_gqa.least_time(cfg, [512], PEAKS)
    assert by_bytes == 1.0
    assert t == pytest.approx(36 * nbytes / 819e9)
    # q and o are 2560 x 4096, k and v 2560 x 1024
    layer = 2560 * 4096 * 2 + 2560 * 1024 * 2 + 3 * 2560 * 9728
    assert model.matmul_params(cfg) == 36 * layer + 2560 * 151936


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        harness.device_peaks("TPU v99 imaginary")
    assert harness.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
