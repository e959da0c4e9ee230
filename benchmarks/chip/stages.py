#!/usr/bin/env python3
"""Where a cell's round time goes, by the program's own stage names.

    python3 benchmarks/chip/stages.py --workload <cell> --seed <n> \
        [--seconds 5] [--out <file>.jsonl]

Builds the cell as a benchmark run does, runs its set-up rounds, then an
untraced window and a traced one of ``--seconds`` each (whole rounds).
Prints one JSON object: both windows' round time (their ratio is what the
profiler costs), device busy and idle ms per round, each ``fl.*`` scope's
device ms per round and their coverage of busy time (the ops' scopes come
from the compiled round's text, see ``trace_scopes.py``), and each
``fl.*`` host span's ms and count per round. Exits non-zero where JAX
finds no TPU.
"""
import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def round_text(engine, src) -> str:
    """The text of the compiled round that ran: lowered again from the
    next round's arguments, it compiles to the same program (from the
    persistent compile cache where one is set)."""
    import jax.numpy as jnp
    batch, mask, _ = src.next()
    return engine._round.lower(
        engine.params, engine.lbg, engine.residual, batch,
        jnp.asarray(mask, jnp.float32)).compile().as_text()


def measure(cell, seed: int, seconds: float, require_tpu: bool = True):
    import jax
    import numpy as np

    import harness
    import trace_reduce
    import trace_scopes
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise harness.NoChip(f"JAX found {devs[0].platform} device(s)")
    gen, fl, data, parts, wkey = harness.inputs(cell, seed)
    engine = harness.build(cell, gen, fl, data, parts)
    p0 = jax.jit(lambda k: cell.model.init(cell.cfg, k))(wkey)
    engine.params = {k: jax.device_put(v, engine.params[k].sharding)
                     for k, v in p0.items()}
    src = engine.prefetcher(np.random.RandomState(fl["seed"] + 1))
    d = tempfile.mkdtemp(prefix="stages-trace-")
    try:
        for _ in range(harness.CHECK_ROUNDS):
            engine.run_round(src)
        jax.block_until_ready(engine.params)
        n0, _, plain_s = harness._window(engine, src, seconds)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            n, _, traced_s = harness._window(engine, src, seconds)
        finally:
            jax.profiler.stop_trace()
        pd = jax.profiler.ProfileData.from_file(
            str(next(Path(d).rglob("*.xplane.pb"))))
        summary = trace_reduce.reduce(pd)
        stages = trace_scopes.reduce(
            pd, trace_scopes.op_names(round_text(engine, src)))
    finally:
        src.close()
        shutil.rmtree(d, ignore_errors=True)
    ms = lambda s: 1e3 * s / n
    busy = trace_reduce.busy_s(summary)
    scopes = sorted({sc for dv in stages["scopes"].values() for sc in dv})
    dev = {sc: ms(trace_scopes.scope_seconds(stages, sc)) for sc in scopes}
    return {
        "workload": cell.name, "seed": seed,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind},
        "rounds": {"plain": n0, "traced": n},
        "round_ms": {"plain": 1e3 * plain_s / n0, "traced": ms(traced_s)},
        "busy_ms": ms(busy), "idle_ms": ms(summary["window_s"] - busy),
        "scope_ms": dev,
        "coverage": sum(dev.values()) / ms(busy) if busy > 0 else None,
        "span_ms": {k: ms(v["s"]) for k, v in stages["host_spans"].items()},
        "span_count": {k: v["n"] / n
                       for k, v in stages["host_spans"].items()},
        "breakdown": trace_reduce.breakdown(summary),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = harness.Cell(ROOT, args.workload)
    try:
        out = measure(cell, args.seed, args.seconds)
    except harness.NoChip as e:
        print(f"stages.py: {e}", file=sys.stderr)
        return 1
    line = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
