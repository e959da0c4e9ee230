"""The control (the reference in bfloat16) and the half-batch fault fail
the limits, which sound runs meet."""
import pytest

from conftest import LIMITS, REPO


@pytest.mark.parametrize("cell", ["tiny-lm-int8", "tiny-fcn"])
def test_control_and_half_batch_fail_the_limits(root, cell):
    import compare
    import control
    import harness
    r = control.readings(harness.Cell(root, cell), seed=5)
    assert not compare.judge(r["control"], LIMITS), r
    assert not compare.judge(r["half_batch"], LIMITS), r


@pytest.mark.parametrize("seed", [5, 2200000002])
def test_control_fails_the_committed_fcn_limits(seed):
    """At the FCN cell's own size, which the CPU holds, the control and the
    half-batch fault fail the limits committed for the cell."""
    import compare
    import control
    import harness
    cell = harness.Cell(REPO, "fcn-paper-k100")
    r = control.readings(cell, seed)
    assert not compare.judge(r["control"], cell.limits), r
    assert not compare.judge(r["half_batch"], cell.limits), r


#: Qwen3's block at a width the CPU holds: every matrix leaf spans several
#: 65,536-entry blocks of the top-k layout, as at the cell's own size
MID_LM = {"hidden_size": 256, "intermediate_size": 512,
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
          "num_hidden_layers": 2, "vocab_size": 1024}


@pytest.mark.parametrize("cell,mix", [("lm-silo-lbgm-int8", "silo-lbgm-int8"),
                                      ("lm-silo-fedavg", "silo-fedavg")])
def test_control_fails_the_committed_lm_limits(root, cell, mix):
    """The LM cell's own traffic at a width the CPU holds: the control and
    the half-batch fault fail the limits committed for the cell."""
    import json

    import compare
    import control
    import harness
    from conftest import TINY_LM
    bench_dir = root / "benchmarks" / "chip"
    (bench_dir / "configs" / "tiny-lm.json").write_text(
        json.dumps(dict(TINY_LM, **MID_LM)))
    traffic = json.loads((REPO / "benchmarks" / "chip" / "traffic"
                          / f"{mix}.json").read_text())
    traffic["data"]["kw"].update(n=64, seq_len=128, vocab=1024)
    traffic["fl"]["num_clients"] = 4
    (bench_dir / "traffic" / "tiny-lm-int8.json").write_text(
        json.dumps(traffic))
    limits = harness.Cell(REPO, cell).limits
    r = control.readings(harness.Cell(root, "tiny-lm-int8"), seed=11)
    assert not compare.judge(r["control"], limits), r
    assert not compare.judge(r["half_batch"], limits), r
