"""The trace reduction against a hand-made trace whose answers are worked
out in its header."""
from pathlib import Path

import pytest

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    import trace_reduce
    text = (DATA / "small_trace.textproto").read_text()
    return trace_reduce.reduce(ProfileData.from_text_proto(text))


def test_window_and_busy_union(summary):
    import trace_reduce
    assert summary["window_s"] == pytest.approx(10e-3)
    devs = summary["devices"]
    assert devs["/device:TPU:0"]["busy_s"] == pytest.approx(4.5e-3)
    assert devs["/device:TPU:1"]["busy_s"] == pytest.approx(6e-3)
    assert trace_reduce.busy_s(summary) == pytest.approx(5.25e-3)


def test_op_seconds_are_clipped_and_averaged(summary):
    import trace_reduce
    # the kernel's own op (1 ms), not custom-call.7 whose HLO text names
    # it; mean over 2 devices
    assert trace_reduce.op_seconds(summary, "lbgm_projection") == \
        pytest.approx(0.5e-3)
    assert summary["devices"]["/device:TPU:1"]["ops"]["fusion.4"] == \
        pytest.approx(1e-3)
    # the module line is not an op line
    assert trace_reduce.op_seconds(summary, "jit_round_fn") == 0
    # ops are keyed by the instruction's name, not its HLO text
    assert set(summary["devices"]["/device:TPU:0"]["ops"]) == {
        "fusion.1", "fusion.2", "lbgm_projection.3", "custom-call.7"}


@pytest.mark.parametrize("name, own", [
    ("lbgm_sparse_decision.12", True),
    ("lbgm_sparse_decision.12.clone", True),
    ("lbgm_sparse_decision", True),
    ("lbgm_sparse_decision_ref.3", False),
    ("fusion.3", False),
    ("custom-call.2", False),
])
def test_op_seconds_matches_kernel_names(name, own):
    import trace_reduce
    summary = {"devices": {"/device:TPU:0": {"ops": {name: 2.0}}}}
    want = 2.0 if own else 0.0
    assert trace_reduce.op_seconds(summary, "lbgm_sparse_decision") == want


@pytest.mark.parametrize("text, name", [
    ("%while.5 = (s32[], f32[2]) while(%tuple.1), condition=%c, body=%b",
     "while.5"),
    ("%lbgm_sparse_decision.3.clone = s32[2,8,128]{2,1,0} custom-call(%p)",
     "lbgm_sparse_decision.3.clone"),
    ("fusion.4", "fusion.4"),
])
def test_op_name_from_hlo_text(text, name):
    import trace_reduce
    assert trace_reduce.op_name(text) == name


def test_gaps_named_by_host_activity(summary):
    assert summary["gaps"] == [["sync", pytest.approx(2.5e-3)],
                               ["prep", pytest.approx(2e-3)],
                               ["sync", pytest.approx(1e-3)]]


def test_breakdown(summary):
    import trace_reduce
    b = trace_reduce.breakdown(summary)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(3.5e-3)]
    assert len(b["device_ops"]) == 5 and len(b["idle_gaps"]) == 3


def test_union_merges_overlaps():
    import numpy as np

    import trace_reduce
    iv = np.array([[5, 6], [0, 2], [1, 3], [3, 4]], float)
    assert trace_reduce._union(iv).tolist() == [[0, 4], [5, 6]]
