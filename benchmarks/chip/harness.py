"""One run of one benchmark cell: set-up, the measured (or traced) window
of whole federated rounds, then the comparison that decides ``correct``.

Everything a cell is made of is found by name under the benchmark's
directory: the configuration file named in ``BENCHMARK.json``, its model
family's ``models/<family>.py`` (plain reference, weights, FLOP count),
``traffic/<mix>.json``, ``limits/<cell>.json`` and one reader
``metrics/<metric>.py`` per per-layer metric.

The run drives the program's own entry: ``build_experiment(spec)``, then
``engine.prefetcher(rng)`` and ``engine.run_round``. Set-up runs the first
``CHECK_ROUNDS`` rounds (the first compiles, or loads from the persistent
cache) and keeps what the comparison reads; the window then runs whole
rounds until ``--seconds`` have passed, each ending in
``block_until_ready`` on the params.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import compare
import reference
import trace_reduce

HERE = Path(__file__).resolve().parent
#: rounds run in set-up and followed by the reference
CHECK_ROUNDS = 3
#: longest traced window (seconds of whole rounds)
TRACE_SECONDS = 5.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def derive_seed(seed: int, stream: int) -> int:
    """A 31-bit seed for one use of the run's seed (any whole number)."""
    ss = np.random.SeedSequence([int(seed) % 2 ** 64, stream])
    return int(ss.generate_state(1)[0] % (2 ** 31 - 1))


class Cell:
    """A cell's files, resolved by name from ``BENCHMARK.json``."""

    def __init__(self, root: Path, name: str):
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"unknown workload {name!r}; have {sorted(cells)}")
        self.root, self.name, self.bench = root, name, bench
        self.spec = cells[name]
        self.chips = int(self.spec["chips"])
        self.dir = root / HERE.relative_to(HERE.parents[1])
        conf = {c["name"]: c for c in bench["configs"]}[self.spec["config"]]
        self.cfg = json.loads((root / conf["file"]).read_text())
        self.model = _load(self.dir / "models" / f"{self.cfg['family']}.py",
                           f"bench_model_{self.cfg['family']}")
        self.traffic = json.loads(
            (self.dir / "traffic" / f"{self.spec['traffic']}.json")
            .read_text())
        self.limits = json.loads(
            (self.dir / "limits" / f"{name}.json").read_text())["limits"]

    def metrics(self, kind: str):
        """This cell's ``end_to_end`` or ``per_layer`` entries."""
        return [m for m in self.bench[kind]
                if name_in(self.name, m.get("workloads"))]

    def reader(self, metric: str):
        """``metrics/<metric>.py``; a metric split by cell kind
        (``<stem>.<kind>``) without a file of its own reads as its stem."""
        path = self.dir / "metrics" / f"{metric}.py"
        if not path.exists():
            path = self.dir / "metrics" / f"{stem(metric)}.py"
        return _load(path, f"bench_metric_{metric.replace('.', '_')}")


def stem(metric: str) -> str:
    """The quantity a metric measures: its name up to the first ``.``. A
    per-layer quantity split by cell kind (``round_mfu`` and
    ``round_mfu.host_bound``) is read alike under each name."""
    return metric.split(".", 1)[0]


def name_in(name, workloads):
    return workloads is None or name in workloads


def inputs(cell: Cell, seed: int):
    """What a run makes from its seed: the generators module, the round's
    FLConfig fields (with the engine's seed), the data, its partition and
    the weights' PRNG key."""
    import jax
    gen = _load(cell.dir / "traffic" / "generators.py", "bench_generators")
    fl = dict(cell.traffic["fl"], seed=derive_seed(seed, 0))
    data, parts = gen.make(cell.traffic, derive_seed(seed, 1),
                           fl["num_clients"])
    return gen, fl, data, parts, jax.random.PRNGKey(derive_seed(seed, 2))


def build(cell: Cell, gen, fl: dict, data, parts, **overrides):
    """The program's engine for the cell, through ``build_experiment``;
    ``overrides`` replace FLConfig fields (the compile rehearsal's)."""
    from repro.fed.experiment import (ComponentSpec, EvalPolicy,
                                      ExperimentSpec, build_experiment)
    from repro.fed.flconfig import FLConfig
    cfg = cell.cfg
    data_name, part_name = gen.register(data, parts)
    spec = ExperimentSpec(
        name=cell.name,
        model=ComponentSpec(cfg["component"], cell.model.program_model(cfg)),
        data=ComponentSpec(data_name),
        partition=ComponentSpec(part_name),
        fl=FLConfig(**dict(fl, **overrides)), rounds=1,
        eval=EvalPolicy(every=0, final=False))
    engine, _ = build_experiment(spec)
    want = {k: tuple(v) for k, v in cell.model.shapes(cfg).items()}
    have = {k: tuple(v.shape) for k, v in engine.params.items()}
    if want != have:
        raise ValueError(f"program params {have} != configuration {want}")
    return engine


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, t0: float | None = None,
        on_engine=None) -> dict:
    """One run; returns the result line's object (``correct`` and all).
    ``on_engine(engine)``, where given, sees the engine before its first
    round (tests plant faults there)."""
    t0 = time.perf_counter() if t0 is None else t0
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        cell.root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    devs = devs[:cell.chips]

    cfg, model, traffic = cell.cfg, cell.model, cell.traffic
    gen, fl, data, parts, wkey = inputs(cell, seed)
    engine = build(cell, gen, fl, data, parts)
    want = {k: tuple(v) for k, v in model.shapes(cfg).items()}
    init = jax.jit(lambda k: model.init(cfg, k))
    p0 = init(wkey)
    engine.params = {k: jax.device_put(v, engine.params[k].sharding)
                     for k, v in p0.items()}
    if on_engine is not None:
        on_engine(engine)

    rng = np.random.RandomState(fl["seed"] + 1)
    src = engine.prefetcher(rng)
    compiles = [0, False]

    def on_event(event, duration, **kw):
        if compiles[1] and event == COMPILE_EVENT:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    prog = {"loss": [], "scalars": [], "wire": []}
    tr = None
    try:
        for i in range(CHECK_ROUNDS):
            m = engine.run_round(src)
            jax.block_until_ready(engine.params)
            prog["loss"].append(m["loss"])
            # every client is sampled: the share times K is the count
            prog["scalars"].append(
                round(m["frac_scalar"] * fl["num_clients"]))
            prog["wire"].append(m["wire_bytes"])
            if i == 0:
                prog["d1"] = _host(reference.leaf_norms(engine.params, p0))
                del p0
        p0 = init(wkey)
        prog["dn"] = _host(reference.leaf_norms(engine.params, p0))
        del p0
        setup_s = time.perf_counter() - t0
        compiles[1] = True
        if trace:
            # the round time ``round_mfu`` divides by comes from a window
            # the profiler does not slow down
            n0, failed0, plain_s = _window(engine, src,
                                           min(seconds, TRACE_SECONDS))
            plain_s /= n0
            tr = _traced_window(engine, src, min(seconds, TRACE_SECONDS))
            rounds, failed, elapsed = tr["window"]
            attempted, failed = rounds + n0, failed + failed0
        else:
            rounds, failed, elapsed = _window(engine, src, seconds)
            attempted = rounds
        compiles[1] = False
    finally:
        src.close()
        jax.monitoring.unregister_event_duration_listener(on_event)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    del engine, src
    gc.collect()

    ref = reference.run(model, cfg, fl, init(wkey), data, parts,
                        fl["seed"], rounds=CHECK_ROUNDS)
    nums = compare.numbers(prog, ref)
    correct = compare.judge(nums, cell.limits) and failed == 0

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        ctx = {"trace": tr["summary"], "rounds": rounds,
               "round_s": plain_s, "chips": len(devs),
               "peak": _peaks(cell.dir, devs[0].device_kind),
               "cfg": cfg, "traffic": traffic, "fl": fl, "model": model,
               "shapes": want}
        metrics = {}
        for m in cell.metrics("per_layer"):
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace_reduce.busy_s(tr["summary"])
        device["window_s"] = tr["summary"]["window_s"]
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = trace_reduce.breakdown(tr["summary"])
    else:
        e2e = {"round_ms": elapsed / rounds * 1e3,
               "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.metrics("end_to_end")}
        out["device"] = device
    out["compiles_in_window"] = compiles[0]
    # each check round's count of clients that sent a scalar: where the
    # gaps grow, a decision taken on the other side of its threshold
    out["scalar_uploads"] = {"program": prog["scalars"],
                             "reference": ref["scalars"]}
    out["checked"] = {k: {"value": nums[k], "limit": lim}
                      for k, lim in cell.limits.items()}
    return out


class NoChip(RuntimeError):
    pass


def _host(tree):
    return {k: float(v) for k, v in tree.items()}


def _window(engine, src, seconds):
    """Whole rounds, each ending in ``block_until_ready`` on the params,
    until ``seconds`` have passed: (rounds, non-finite losses, seconds).
    The spans cost nothing unless the profiler is on."""
    import jax
    rounds = failed = 0
    start = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        while time.perf_counter() - start < seconds or not rounds:
            with jax.profiler.TraceAnnotation("bench.round"):
                m = engine.run_round(src)
                jax.block_until_ready(engine.params)
            rounds += 1
            failed += not math.isfinite(m["loss"])
    return rounds, failed, time.perf_counter() - start


def _traced_window(engine, src, seconds):
    """The window under the profiler, and the trace reduced (then
    deleted)."""
    import jax
    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            window = _window(engine, src, seconds)
        finally:
            jax.profiler.stop_trace()
        path = next(Path(d).rglob("*.xplane.pb"))
        return {"window": window, "summary": trace_reduce.reduce(str(path))}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _peaks(bench_dir: Path, kind: str) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise ValueError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def report(out: dict) -> None:
    """The numbers compared, on standard error, then the result line."""
    for k, v in out["checked"].items():
        print(f"{k} {v['value']:.6g} limit {v['limit']:.6g}", file=sys.stderr)
    print(json.dumps(out), flush=True)
