#!/usr/bin/env python3
"""Readings that set the upper end of each ``correct`` limit.

For each seed, at the cell's own size and from the same weights and data
as a benchmark run of that seed, the plain reference (float32, HIGHEST)
is compared, by the numbers of ``compare.py``, with

* ``control``: the reference computed in bfloat16, the next precision
  below the configuration's float32;
* ``half_batch``: the reference with half of every batch left out, the
  mean taken over the rest (a training cell's fault).

A state left unchanged reads 1 on ``grad1_gap`` and ``change_gap`` by
construction and needs no run. The benchmark's own runs never run this.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 \
        [--out <file>.jsonl]
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def readings(cell, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    import compare
    import harness
    import reference
    _, fl, data, parts, wkey = harness.inputs(cell, seed)
    init = jax.jit(lambda k: cell.model.init(cell.cfg, k))
    args = (cell.model, cell.cfg, fl, init(wkey), data, parts, fl["seed"])
    ref = reference.run(*args, rounds=harness.CHECK_ROUNDS)
    out = {}
    for name, kw in (("control", {"dtype": jnp.bfloat16}),
                     ("half_batch", {"batch_frac": 0.5})):
        other = reference.run(*args, rounds=harness.CHECK_ROUNDS, **kw)
        out[name] = compare.numbers(other, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", help="also append each line to this file")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 1
    cell = harness.Cell(ROOT, args.workload)
    for seed in args.seeds:
        line = json.dumps({"workload": args.workload, "seed": seed,
                           **readings(cell, seed)})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
