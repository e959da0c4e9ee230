"""The yardstick's counts on hand-worked shapes."""
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]


def test_decision_bytes_single_block_leaf():
    import counts
    # size 100, k_frac 0.1: one block of 100, kb 10 -> 4*100 + 16*10
    assert counts.decision_bytes({"w": (10, 10)}, 0.1, 1) == 560


def test_decision_bytes_counts_no_pad_rows():
    import counts
    # size 200000: block 65536, 4 rows hold data (nb rounds up to 16),
    # k = 20000, kb = 20000 // 16 = 1250 -> 4*200000 + 16*4*1250
    assert counts.decision_bytes({"w": (200000,)}, 0.1, 3) == \
        3 * (800000 + 80000)


def test_projection_bytes():
    import counts
    assert counts.projection_bytes({"a": (784, 128), "b": (10,)}, 100) == \
        2 * 4 * (784 * 128 + 10) * 100


def test_fcn_flops_are_six_per_parameter():
    import counts
    import harness
    fcn = harness._load(BENCH / "models" / "fcn.py", "t_fcn")
    cfg = json.loads((BENCH / "configs" / "paper-fcn.json").read_text())
    fl = {"num_clients": 100, "tau": 2, "batch_size": 32}
    assert counts.round_model_flops(fcn, cfg, fl, {}) == \
        6 * 101770 * 100 * 2 * 32


def test_qwen3_flops_by_hand():
    import harness
    q = harness._load(BENCH / "models" / "qwen3.py", "t_qwen3")
    cfg = {"num_hidden_layers": 2, "hidden_size": 8, "head_dim": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "intermediate_size": 16, "vocab_size": 10}
    traffic = {"data": {"kw": {"seq_len": 5}}}
    # per layer: q,o 8x8 each, k,v 8x4 each, ffn 3*8*16 -> 576; head 80
    matmul = 2 * 576 + 80
    attn = 12 * 2 * 5 * 8
    assert q.train_flops_per_sample(cfg, traffic) == \
        pytest.approx(5 * (6 * matmul + attn))


def test_peaks_know_the_v5e():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
