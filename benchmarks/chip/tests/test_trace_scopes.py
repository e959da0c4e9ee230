"""The split of a trace by the program's stage names, against a hand-made
trace and compiled module text whose answers are worked out in the trace's
header; and the stage split of a tiny cell's run on the CPU."""
from pathlib import Path

import pytest

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def stages():
    from jax.profiler import ProfileData

    import trace_scopes
    text = (DATA / "scoped_trace.textproto").read_text()
    names = trace_scopes.op_names((DATA / "scoped_round.hlo").read_text())
    return trace_scopes.reduce(ProfileData.from_text_proto(text), names)


def test_scopes_are_unions_per_device(stages):
    ms = lambda s: pytest.approx(s * 1e-3)
    assert stages["scopes"] == {
        "/device:TPU:0": {"fl.client_sgd": ms(4), "fl.decision": ms(2),
                          "fl.codec": ms(0.5), "fl.fold": ms(2.5)},
        "/device:TPU:1": {"fl.fold": ms(3)}}


@pytest.mark.parametrize("scope, ms", [
    ("fl.client_sgd", 2.0), ("fl.decision", 1.0), ("fl.codec", 0.25),
    ("fl.fold", 2.75), ("fl.other", 0.0)])
def test_scope_seconds_mean_over_devices(stages, scope, ms):
    import trace_scopes
    assert trace_scopes.scope_seconds(stages, scope) == \
        pytest.approx(ms * 1e-3)


@pytest.mark.parametrize("name, ms, n", [
    ("fl.prefetch_wait", 2.0, 2), ("fl.readback", 1.0, 1),
    ("fl.host_prep", 1.0, 1)])
def test_host_spans_clipped_to_window(stages, name, ms, n):
    import trace_scopes
    assert trace_scopes.span_seconds(stages, name) == pytest.approx(ms * 1e-3)
    assert stages["host_spans"][name]["n"] == n


def test_spans_outside_window_or_not_stages_absent(stages):
    import trace_scopes
    assert set(stages["host_spans"]) == {
        "fl.prefetch_wait", "fl.readback", "fl.host_prep"}
    assert trace_scopes.span_seconds(stages, "fl.h2d") == 0.0


def test_op_names_of_module_text():
    import trace_scopes
    names = trace_scopes.op_names((DATA / "scoped_round.hlo").read_text())
    assert names["while.9"] == "jit(round_fn)/while"
    assert names["convert.3"].endswith("vmap(fl.codec)/convert_element_type")
    assert {"fusion.7", "lbgm_sparse_decision.3", "fusion.2"} <= set(names)
    # an instruction with no metadata has no op_name
    assert "copy.6" not in names and "p.1" not in names


@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/vmap(fl.client_sgd)/while", "fl.client_sgd"),
    ("jit(f)/fl.client_sgd/transpose(jvp(fl.fold))/x", "fl.fold"),
    ("jit(f)/while/body/add", None),
    ("", None),
    ("jit(f)/fl.h2d/x", "fl.h2d"),
])
def test_op_scope(op_name, scope):
    import trace_scopes
    assert trace_scopes.op_scope(op_name) == scope


@pytest.mark.parametrize("cell", ["tiny-fcn", "tiny-lm-int8"])
def test_stages_on_a_tiny_cell(root, cell):
    """On the CPU the trace has no device plane, so no scope reads; the
    host spans of the traced window do, each round once at least."""
    import harness
    import stages
    out = stages.measure(harness.Cell(root, cell), 3, 0.2,
                         require_tpu=False)
    assert out["scope_ms"] == {} and out["coverage"] is None
    assert out["rounds"]["traced"] >= 1 and out["round_ms"]["plain"] > 0
    for name in ("fl.prefetch_wait", "fl.host_prep", "fl.h2d",
                 "fl.readback"):
        assert out["span_count"][name] >= 1, name


def test_round_text_names_every_scope(root):
    """The compiled round's text, as ``stages.py`` reads it after a run,
    names the program's four device scopes."""
    import numpy as np

    import harness
    import stages
    import trace_scopes
    cell = harness.Cell(root, "tiny-lm-int8")
    engine = harness.build(cell, *harness.inputs(cell, 3)[:4])
    src = engine.prefetcher(np.random.RandomState(0))
    try:
        engine.run_round(src)
        names = trace_scopes.op_names(stages.round_text(engine, src))
    finally:
        src.close()
    assert {trace_scopes.op_scope(n) for n in names.values()} >= {
        "fl.client_sgd", "fl.decision", "fl.codec", "fl.fold"}
