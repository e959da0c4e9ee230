#!/usr/bin/env python3
"""Run one cell several times in a row, each run a process of its own, and
append each run's result line to a JSON-lines file.

    python3 benchmarks/chip/series.py --workload <cell> --seeds 1 2 3 \
        --seconds 10 [--trace 0|1] --out <file>.jsonl

For measuring spreads and proving seeds on the chip. The process itself
never imports JAX, so each child gets the chip.
"""
import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    worst = 0
    for seed in args.seeds:
        t = time.time()
        p = subprocess.run(
            [sys.executable, str(RUN), "--workload", args.workload,
             "--seed", str(seed), "--seconds", str(args.seconds),
             "--trace", str(args.trace)],
            capture_output=True, text=True)
        lines = p.stdout.strip().splitlines()
        rec = {"workload": args.workload, "seed": seed, "trace": args.trace,
               "rc": p.returncode, "wall_s": time.time() - t}
        try:
            rec["result"] = json.loads(lines[-1])
        except (IndexError, json.JSONDecodeError):
            rec["stderr"] = p.stderr[-4000:]
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        res = rec.get("result", {})
        print(json.dumps({k: rec[k] for k in ("seed", "rc", "wall_s")}
                         | {"correct": res.get("correct"),
                            "metrics": {k: v["value"] for k, v in
                                        res.get("metrics", {}).items()},
                            "checked": {k: v["value"] for k, v in
                                        res.get("checked", {}).items()}}),
              flush=True)
        if "stderr" in rec:
            print(rec["stderr"][-3000:], flush=True)
        worst = max(worst, p.returncode)
    return worst


if __name__ == "__main__":
    sys.exit(main())
