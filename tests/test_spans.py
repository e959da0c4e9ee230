"""The round's stage names reach a profiler trace, and change nothing the
round computes.

* every ``fl.*`` named scope a configuration runs is in the ``op_name``
  metadata of its compiled round, on each scheduler;
* an engine built with the scopes turned into no-ops (the round as it was
  before they existed) gives the same history and parameters, bit for bit;
* the four host spans land on the host plane of a profiler trace.
"""
import contextlib
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fed import engine as engine_mod
from repro.fed import spans
from repro.fed.experiment import ExperimentSpec, build_experiment

LM_INT8 = {
    "name": "spans-lm-int8",
    "model": {"name": "lm", "kw": {"arch": "qwen3-1.7b", "reduced": True,
                                   "vocab_size": 64}},
    "data": {"name": "markov", "kw": {"n": 32, "n_eval": 0, "seq_len": 16,
                                      "vocab": 64}},
    "partition": {"name": "iid", "kw": {}},
    "fl": {"num_clients": 4, "tau": 2, "lr": 0.5, "batch_size": 2,
           "use_lbgm": True, "delta_threshold": 0.5, "lbg_variant": "topk",
           "lbg_kw": {"k_frac": 0.05}, "scheduler": "chunked",
           "chunk_size": 2, "codec": "int8", "seed": 0},
    "rounds": 1, "eval": {"every": 0, "final": False},
}
FCN = {
    "name": "spans-fcn",
    "model": {"name": "fcn", "kw": {}},
    "data": {"name": "mixture", "kw": {"n": 600, "n_eval": 0}},
    "partition": {"name": "label_skew", "kw": {"classes_per_client": 3}},
    "fl": {"num_clients": 6, "tau": 2, "lr": 0.05, "batch_size": 8,
           "use_lbgm": True, "delta_threshold": 0.2, "lbg_variant": "dense",
           "scheduler": "vmap", "codec": "none", "seed": 0},
    "rounds": 1, "eval": {"every": 0, "final": False},
}
DEVICE = (spans.CLIENT_SGD, spans.DECISION, spans.FOLD)
#: configuration -> (spec, FLConfig overrides, scopes its round runs); the
#: FCN's codec is "none", whose encode is no op at all
CASES = {
    "lm-int8-chunked": (LM_INT8, {}, spans.SCOPES),
    "fcn-vmap": (FCN, {}, DEVICE),
    "fcn-topk-sharded": (FCN, {"scheduler": "sharded", "chunk_size": 3,
                               "lbg_variant": "topk-sharded",
                               "lbg_kw": {"k_frac": 0.25}}, DEVICE),
    "fcn-topk-buffered": (FCN, {"scheduler": "buffered", "chunk_size": 3,
                                "lbg_variant": "topk",
                                "lbg_kw": {"k_frac": 0.25}}, DEVICE),
}
OP_NAME = re.compile(r'op_name="([^"]*)"')
#: a scope's segment, bare or under a transform (``vmap(fl.client_sgd)``)
SCOPE = re.compile(r"\bfl\.\w+")


def build(name):
    spec, over, _ = CASES[name]
    fl = dict(spec["fl"], **over)
    engine, _ = build_experiment(ExperimentSpec.from_dict(dict(spec, fl=fl)))
    return engine


def round_scopes(engine) -> set:
    """The ``fl.*`` segments of the compiled round's op metadata."""
    rng = np.random.RandomState(0)
    batch = engine._sample_batches(rng)
    mask = engine._sample_mask(rng)
    if isinstance(mask, dict):   # the buffered scheduler's delivery plan
        args = (engine._buffer, batch) + tuple(
            jnp.asarray(mask[k], jnp.float32)
            for k in ("dispatch", "deliver", "stale"))
    else:
        args = (batch, jnp.asarray(mask, jnp.float32))
    text = engine._round.lower(engine.params, engine.lbg, engine.residual,
                               *args).compile().as_text()
    return {seg for name in OP_NAME.findall(text)
            for seg in SCOPE.findall(name)}


@pytest.mark.parametrize("name", sorted(CASES))
def test_scopes_in_compiled_round(name):
    assert round_scopes(build(name)) >= set(CASES[name][2])


def run(engine, rounds=3):
    rng = np.random.RandomState(1)
    hist = [engine.run_round(rng) for _ in range(rounds)]
    return hist, jax.device_get(engine.params)


@pytest.mark.parametrize("name", ["lm-int8-chunked", "fcn-vmap"])
def test_scopes_change_nothing(name, monkeypatch):
    hist, params = run(build(name))
    with monkeypatch.context() as m:
        m.setattr(engine_mod, "scope", lambda _: contextlib.nullcontext())
        before = build(name)
        # after the rounds: drawing a batch advances the codec's stream
        hist0, params0 = run(before)
        assert round_scopes(before) == set()
    assert hist == hist0
    for k in params:
        np.testing.assert_array_equal(params[k], params0[k], err_msg=k)


def test_host_spans_on_host_plane(tmp_path):
    from jax.profiler import ProfileData
    engine = build("fcn-vmap")
    src = engine.prefetcher(np.random.RandomState(0))
    try:
        engine.run_round(src)    # compiles outside the trace
        with jax.profiler.trace(str(tmp_path)):
            for _ in range(2):
                engine.run_round(src)
                jax.block_until_ready(engine.params)
    finally:
        src.close()
    pd = ProfileData.from_file(str(next(Path(tmp_path).rglob("*.xplane.pb"))))
    names = {ev.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert set(spans.SPANS) <= names
