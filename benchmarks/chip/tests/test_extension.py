"""A new cell made of new files only: a configuration, a traffic mix and a
per-layer metric reader, plus BENCHMARK.json entries, runs with no edit to
any file the benchmark already has."""
import json

from conftest import TINY_FCN, run_cell


def test_new_files_make_a_runnable_cell(root):
    bench_dir = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    cfg = dict(TINY_FCN, hidden_size=24)
    (bench_dir / "configs" / "ext-fcn.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench_dir / "traffic" / "tiny-fcn.json").read_text())
    traffic["fl"]["num_clients"] = 4
    traffic["partition"]["kw"] = {"classes_per_client": 4}
    (bench_dir / "traffic" / "ext-mix.json").write_text(json.dumps(traffic))
    (bench_dir / "metrics" / "ext_rounds.py").write_text(
        "def read(ctx):\n    return float(ctx['rounds'])\n")
    (bench_dir / "limits" / "ext-cell.json").write_text(json.dumps(
        {"limits": {"loss_gap": 1e-3, "grad1_gap": 1e-3,
                    "change_gap": 1e-3}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "ext-fcn", "source": "test",
                             "file": "benchmarks/chip/configs/ext-fcn.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "ext-cell", "config": "ext-fcn",
                               "traffic": "ext-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "ext_rounds", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "harness", "moves": "round_ms",
                               "workloads": ["ext-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = run_cell(root, "ext-cell", trace=True)
    assert out["correct"], out["checked"]
    # the traced rounds; ``attempted`` also counts the untraced window
    assert 1 <= out["metrics"]["ext_rounds"]["value"] < out["attempted"]
    assert all(p.read_bytes() == b for p, b in before.items())
