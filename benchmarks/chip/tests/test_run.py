"""Whole runs of tiny cells on the CPU: sound runs are correct, and a run
with the timed path broken underneath is not."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO, run_cell
from harness import Cell, stem

SOUND = ["tiny-lm-int8", "tiny-lm-fedavg", "tiny-lm-int8-blocks", "tiny-fcn"]


@pytest.mark.parametrize("cell", SOUND)
def test_sound_run_is_correct(root, cell):
    out = run_cell(root, cell)
    assert out["correct"], out["checked"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    want = {m["name"] for m in Cell(root, cell).metrics("end_to_end")}
    assert set(out["metrics"]) == want
    assert {"peak_hbm_gib", "setup_s"} <= want
    assert list(out)[-1] == "checked"
    assert out["compiles_in_window"] == 0


def _freeze(engine):
    """A round that returns the params it was given."""
    step = engine._round

    def frozen(params, lbg, residual, batch, mask):
        _, lbg, residual, metrics = step(params, lbg, residual, batch, mask)
        return params, lbg, residual, metrics
    engine._round = frozen


@pytest.mark.parametrize("cell", ["tiny-lm-int8", "tiny-fcn"])
def test_state_left_unchanged_is_not_correct(root, cell):
    out = run_cell(root, cell, on_engine=_freeze)
    assert not out["correct"]
    assert out["checked"]["grad1_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", ["tiny-lm-int8", "tiny-fcn"])
def test_half_batch_is_not_correct(root, cell, monkeypatch):
    from repro.fed.engine import FLEngine
    make = FLEngine._make_client_update

    def half(self, hetero_tau=False):
        update = make(self, hetero_tau)

        def client_update(params, batches, *rest):
            cut = {k: v[:, :v.shape[1] // 2] for k, v in batches.items()}
            return update(params, cut, *rest)
        return client_update

    monkeypatch.setattr(FLEngine, "_make_client_update", half)
    out = run_cell(root, cell)
    assert not out["correct"], out["checked"]


def test_traced_run_reports_per_layer_metrics(root):
    out = run_cell(root, "tiny-fcn", trace=True)
    assert out["correct"]
    want = {m["name"] for m in Cell(root, "tiny-fcn").metrics("per_layer")}
    assert {"round_mfu", "device_idle_share"} <= {stem(n) for n in want}
    assert set(out["metrics"]) <= want
    # a host-bound cell's round time, reported per layer
    assert out["metrics"]["round_ms.host_bound"]["value"] > 0
    assert "busy_s" in out["device"] and out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_chip_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "fcn-paper-k100", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no program to run."""
    import shutil
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "fcn-paper-k100", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()
    json.loads((tmp_path / "BENCHMARK.json").read_text())


def _never_scalar(engine):
    """A decision that never recycles the look-back gradient."""
    engine.store.delta = 0.0


def _values_priced_as_fp32(monkeypatch):
    from repro.comm.wire import Int8Codec
    monkeypatch.setattr(Int8Codec, "value_bytes", 4.0)


@pytest.mark.parametrize("cell,number", [("tiny-fcn", "scalar_gap"),
                                         ("tiny-lm-int8", "wire_gap")])
def test_wrong_decision_or_byte_count_is_not_correct(root, cell, number,
                                                     monkeypatch):
    """The scalar decisions and the wire bytes are compared, not only the
    parameters: a decision taken against another threshold, or int8
    values priced at four bytes, fails its number."""
    if number == "wire_gap":
        _values_priced_as_fp32(monkeypatch)
        out = run_cell(root, cell)
    else:
        out = run_cell(root, cell, on_engine=_never_scalar)
    assert not out["correct"]
    assert out["checked"][number]["value"] > out["checked"][number]["limit"]
