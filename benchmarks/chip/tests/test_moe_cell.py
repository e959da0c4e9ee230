"""The DeepSeek-V3 family file in whole runs of a tiny cell on the CPU: a
sound run is correct, and the half-batch fault and a state left unchanged
are not; a traced run is correct too."""
import json

import pytest

import conftest
from conftest import run_cell
from test_run import _freeze

#: the committed cell has no tiny stand-in in the shared fixture; its
#: per-layer metrics are read by this file's own cell
conftest.STANDS_IN.setdefault("moe-silo-lbgm-int8", [])

#: the configuration's keys at a size the CPU holds: 4 of 8 routed experts
#: held (offset 2), top-3, 2 shared experts, one leading dense layer
TINY_MOE = {"source": "test", "family": "deepseek_v3", "component": "lm",
            "hidden_size": 64, "intermediate_size": 96,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_interleave": True,
            "moe_intermediate_size": 24, "n_routed_experts": 4,
            "held_expert_offset": 2, "published": {"n_routed_experts": 8},
            "num_experts_per_tok": 3, "n_shared_experts": 2,
            "norm_topk_prob": True, "routed_scaling_factor": 2.448,
            "scoring_func": "sigmoid", "first_k_dense_replace": 1,
            "num_hidden_layers": 3, "vocab_size": 96, "rms_norm_eps": 1e-6,
            "rope_theta": 1000000, "tie_word_embeddings": False,
            "initializer_range": 0.02, "dtype": "float32", "reduced": []}
#: the committed cell's traffic at the tiny size (chunk 1, as committed)
TRAFFIC = {"data": {"name": "markov", "kw": {"n": 32, "seq_len": 16,
                                             "vocab": 96}},
           "partition": {"name": "iid", "kw": {}},
           "fl": {"num_clients": 4, "tau": 2, "lr": 0.5, "batch_size": 2,
                  "use_lbgm": True, "delta_threshold": 0.5,
                  "lbg_variant": "topk", "lbg_kw": {"k_frac": 0.05},
                  "scheduler": "chunked", "chunk_size": 1, "codec": "int8"}}
CELL = "tiny-moe-int8"
#: on the CPU, seeds 3, 5 and 9: sound runs read at most 5.1e-5 (loss),
#: 2.4e-4 (first update), 2.5e-3 (change after three rounds: int8 rounding
#: and top-k membership follow the routes) and 1.1e-4 (wire); the bfloat16
#: control reads at least 0.056 / 0.083 on the two norms, the half batch
#: 0.25 / 0.31
LIMITS = {"loss_gap": 1e-3, "grad1_gap": 3e-3, "change_gap": 1e-2,
          "scalar_gap": 0.0, "wire_gap": 1e-3}


@pytest.fixture
def moe_root(root):
    bench_dir = root / "benchmarks" / "chip"
    (bench_dir / "configs" / "tiny-moe.json").write_text(json.dumps(TINY_MOE))
    (bench_dir / "traffic" / "tiny-moe-int8.json").write_text(
        json.dumps(TRAFFIC))
    (bench_dir / "limits" / f"{CELL}.json").write_text(
        json.dumps({"limits": LIMITS}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "benchmarks/chip/configs/tiny-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-moe",
                               "traffic": "tiny-moe-int8", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and m["name"] == "round_ms":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_sound_run_is_correct_and_traced(moe_root):
    out = run_cell(moe_root, CELL, trace=True)
    assert out["correct"], out["checked"]
    assert out["failed"] == 0 and out["compiles_in_window"] == 0


def test_state_left_unchanged_is_not_correct(moe_root):
    out = run_cell(moe_root, CELL, on_engine=_freeze)
    assert not out["correct"]
    assert out["checked"]["grad1_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_not_correct(moe_root, monkeypatch):
    from repro.fed.engine import FLEngine
    make = FLEngine._make_client_update

    def half(self, hetero_tau=False):
        update = make(self, hetero_tau)

        def client_update(params, batches, *rest):
            cut = {k: v[:, :v.shape[1] // 2] for k, v in batches.items()}
            return update(params, cut, *rest)
        return client_update

    monkeypatch.setattr(FLEngine, "_make_client_update", half)
    out = run_cell(moe_root, CELL)
    assert not out["correct"], out["checked"]

