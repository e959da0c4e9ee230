"""Shared fixtures: a copy of the benchmark in a temporary checkout with
tiny cells, run on the CPU.

Run these tests by path from the repository root:

    python -m pytest -q benchmarks/chip/tests
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(BENCH))

TINY_LM = {"source": "test", "family": "qwen3", "component": "lm",
           "hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 64,
           "rms_norm_eps": 1e-6, "rope_theta": 1000000,
           "tie_word_embeddings": True, "initializer_range": 0.02,
           "dtype": "float32", "reduced": []}
#: the embedding (1100 x 64 = 70,400 entries) spans two 65,536-entry
#: blocks of the top-k layout, which pads it to 16 block rows
TINY_LM_BLOCKS = dict(TINY_LM, vocab_size=1100)
TINY_FCN = {"source": "test", "family": "fcn", "component": "fcn",
            "input_size": 784, "hidden_size": 16, "num_classes": 10,
            "dtype": "float32", "reduced": []}
TRAFFIC = {
    "lm-int8": {"data": {"name": "markov",
                         "kw": {"n": 32, "seq_len": 16, "vocab": 64}},
                "partition": {"name": "iid", "kw": {}},
                "fl": {"num_clients": 4, "tau": 2, "lr": 0.5,
                       "batch_size": 2, "use_lbgm": True,
                       "delta_threshold": 0.5, "lbg_variant": "topk",
                       "lbg_kw": {"k_frac": 0.05}, "scheduler": "chunked",
                       "chunk_size": 2, "codec": "int8"}},
    "lm-fedavg": {"data": {"name": "markov",
                           "kw": {"n": 32, "seq_len": 16, "vocab": 64}},
                  "partition": {"name": "iid", "kw": {}},
                  "fl": {"num_clients": 4, "tau": 2, "lr": 0.5,
                         "batch_size": 2, "use_lbgm": False,
                         "scheduler": "chunked", "chunk_size": 2,
                         "codec": "none"}},
    "lm-int8-blocks": {"data": {"name": "markov",
                                "kw": {"n": 32, "seq_len": 16, "vocab": 1100}},
                       "partition": {"name": "iid", "kw": {}},
                       "fl": {"num_clients": 4, "tau": 2, "lr": 0.5,
                              "batch_size": 2, "use_lbgm": True,
                              "delta_threshold": 0.5, "lbg_variant": "topk",
                              "lbg_kw": {"k_frac": 0.01},
                              "scheduler": "chunked", "chunk_size": 2,
                              "codec": "int8"}},
    "fcn": {"data": {"name": "mixture", "kw": {"n": 600}},
            "partition": {"name": "label_skew",
                          "kw": {"classes_per_client": 3}},
            "fl": {"num_clients": 6, "tau": 2, "lr": 0.05,
                   "batch_size": 8, "use_lbgm": True,
                   "delta_threshold": 0.2, "lbg_variant": "dense",
                   "scheduler": "vmap", "codec": "none"}},
}
CELLS = {"tiny-lm-int8": ("tiny-lm", "lm-int8"),
         "tiny-lm-fedavg": ("tiny-lm", "lm-fedavg"),
         "tiny-lm-int8-blocks": ("tiny-lm-blocks", "lm-int8-blocks"),
         "tiny-fcn": ("tiny-fcn", "fcn")}
#: the tiny cells that stand in for each cell of BENCHMARK.json, and so
#: report the metrics that list it
STANDS_IN = {"lm-silo-lbgm-int8": ["tiny-lm-int8", "tiny-lm-int8-blocks"],
             "lm-silo-fedavg": ["tiny-lm-fedavg"],
             "fcn-paper-k100": ["tiny-fcn"]}
#: tiny cells on the CPU, seeds 3, 5 and 9: sound runs read at most 9e-5,
#: 2e-5 and 2.4e-3 (the change after three rounds swings with int8
#: rounding and decisions near their threshold); the bfloat16 control
#: reads at least 1.1e-4 / 8e-4 and 0.05 / 0.0014 (LM / FCN), with its loss
#: gap over 2e-3 on the FCN
LIMITS = {"loss_gap": 1e-3, "grad1_gap": 1e-4, "change_gap": 1e-2,
          "scalar_gap": 0.0, "wire_gap": 1e-3}


def make_root(tmp: Path) -> Path:
    """A checkout holding the benchmark, with tiny cells in place of the
    chip-sized ones."""
    dst = tmp / "benchmarks" / "chip"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    for name, cfg in (("tiny-lm", TINY_LM), ("tiny-lm-blocks", TINY_LM_BLOCKS),
                      ("tiny-fcn", TINY_FCN)):
        (dst / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, t in TRAFFIC.items():
        (dst / "traffic" / f"tiny-{name}.json").write_text(json.dumps(t))
    for name in CELLS:
        (dst / "limits" / f"{name}.json").write_text(
            json.dumps({"limits": LIMITS}))
    peaks = json.loads((dst / "peaks.json").read_text())
    # the CPU is not a device the benchmark measures; the tests need some
    # peak to divide by
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    (dst / "peaks.json").write_text(json.dumps(peaks))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [
        {"name": n, "source": "test", "file": f"benchmarks/chip/configs/{n}.json",
         "reduced": [], "why": "test"}
        for n in ("tiny-lm", "tiny-lm-blocks", "tiny-fcn")]
    bench["workloads"] = [
        {"name": c, "config": cfg, "traffic": f"tiny-{t}", "chips": 1,
         "why": "test"} for c, (cfg, t) in CELLS.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [t for w in m["workloads"] for t in STANDS_IN[w]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def root(tmp_path):
    return make_root(tmp_path)


def run_cell(root: Path, name: str, seed: int = 3, trace: bool = False,
             on_engine=None) -> dict:
    """One run of a tiny cell on the CPU, past the harness's chip check."""
    import harness
    return harness.run(harness.Cell(root, name), seed, 0.2, trace,
                       require_tpu=False, on_engine=on_engine)
