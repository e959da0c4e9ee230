#!/usr/bin/env python3
"""Chip benchmark of the LBGM federated round: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The cells, metrics and bounds are in
``BENCHMARK.json``; see ``harness.py`` for what a run does. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; last comes ``checked``, each number compared with its
limit (also the last lines of standard error). Exits non-zero, printing
no result, where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    cell = harness.Cell(ROOT, args.workload)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t0=T0)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
