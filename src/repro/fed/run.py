"""CLI entry point for the declarative experiment API.

    PYTHONPATH=src python -m repro.fed.run --spec spec.json \
        --set fl.delta_threshold=0.4 --set model.name=cnn --rounds 20

Without ``--spec`` a small built-in spec runs (4-client FCN on the mixture
dataset) — handy as a smoke test and as a template: ``--print-spec`` dumps
the fully resolved spec as JSON without running, so

    python -m repro.fed.run --print-spec > spec.json

bootstraps a spec file you can edit and feed back in. ``--set`` takes
dotted keys into the spec (``fl.*``, ``model.kw.*``, ...); values parse as
JSON when possible, else as strings.

Multi-device execution rides the same knobs: ``--set
fl.scheduler=sharded --set fl.mesh=4`` runs each chunk's clients
data-parallel on a 4-device client mesh, and ``--set "fl.mesh=[2,4]"``
asks for the 2-D (clients, model) mesh — 2-way client parallelism with
the LBGM banks/decision sharded 4 ways along the model axis (an int
mesh ``n`` is exactly ``[n, 1]``; force host devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU). See
``examples/specs/yi34b_mesh2x4.json`` for a full 2-D large-arch spec.

``--set fl.model_sharding=auto`` additionally runs each client's
local-SGD forward/backward tensor-parallel along the model axis
(default ``replicate`` keeps it replicated — bit-for-bit the pre-knob
engine). Requires ``fl.scheduler=sharded``, a model component that
carries sharding metadata (``model.name=lm``; fcn/cnn refuse),
``fl.lbg_variant=topk-sharded`` and ``fl.compressor=none``; histories
match ``replicate`` at fp32 tolerance with identical uplink
accounting. ``examples/specs/yi34b_tp2x4.json`` runs the full yi-34b
layer count (width-reduced) tensor-parallel on a 2x4 mesh.

The uplink wire codec rides the same knobs: ``--set fl.codec=int8``
(or ``fp8`` / ``delta_idx``) quantizes the sparse LBGM payloads to ~1
byte/value with per-block-row power-of-two scales and varint-delta
indices — needs the sparse payload path (``fl.lbg_variant=topk`` or
``topk-sharded``) or vanilla FL (``fl.use_lbgm=false``); ``--set
"fl.codec_kw={\"stochastic\": false}"`` switches to nearest rounding.
``codec=none`` (default) ships fp32 bit-for-bit. Real bytes land in the
history as ``wire_bytes`` / ``wire_savings`` (see ``repro.comm.wire``
for the wire format); ``examples/specs/quantized_lbgm.json`` is a full
int8 LBGM spec.

Buffered async aggregation (FedBuff-style) rides the same knobs:
``--set fl.scheduler=buffered --set fl.latency=straggler --set
"fl.latency_kw={\"frac\": 0.2, \"delay\": 4}"`` treats slow clients as
*latency* instead of dropout — a dispatched payload sits in flight for a
model-drawn number of rounds and folds into the global update in its
arrival round, discounted by ``1/(1+staleness)**alpha``. Latency models:
``none`` (default; with it, buffered is bit-for-bit the chunked
scheduler), ``fixed``, ``uniform``, ``lognormal``, ``straggler`` (fixed
seed-derived slow cohort; ``drop=true`` makes the cohort never deliver —
the dropout baseline — and ``slow_tau`` gives it a smaller local-step
budget). Needs the sparse payload path (``fl.lbg_variant=topk`` /
``topk-sharded``); wire/uplink bytes are attributed to the arrival
round. ``examples/specs/async_buffered.json`` is a full spec;
``benchmarks/async_heterogeneity.py`` is the dropout-vs-buffered grid.
``"fl.latency_kw={\"max_staleness\": 8}"`` (any model) evicts in-flight
payloads older than 8 rounds instead of parking them forever; evictions
land in the ledger as ``n_evicted``.

Out-of-core client banks: ``--set fl.lbg_variant=topk-host`` keeps the
per-client LBG banks host-resident (NumPy) and streams one chunk's bank
to the device per scan step on a background thread — device bank memory
is O(chunk_size), independent of ``num_clients``, bit-for-bit equal to
``topk``. Needs ``fl.scheduler=chunked``, a streaming aggregator
(``mean``), and no error feedback. ``examples/specs/hier_100k.json``
runs a 100k-client round this way.

Hierarchical aggregation: ``--set "fl.tiers=[32,4]"`` routes clients
through 32 edge aggregators and 4 regions before the global server
(contiguous balanced assignment; ``--set "fl.tiers={\"levels\": [32,4],
\"assign\": \"shuffle\"}"`` for a seed-derived shuffle). Histories stay
bit-for-bit the flat fold (see ``repro.fed.hierarchy``); the ledger
gains per-tier wire bytes (``tier_wire_bytes``).

Checkpoint/resume: ``--set fl.ckpt_every=10 --set
fl.ckpt_path=run.ckpt.npz`` atomically checkpoints params, LBG banks,
rng streams, buffered in-flight slots and the comm ledger every 10
rounds; re-running with ``--resume`` picks up from the latest
checkpoint and finishes with a history bit-for-bit equal to the
uninterrupted run.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

from repro.compile_cache import enable_compile_cache
from repro.fed.experiment import (ComponentSpec, EvalPolicy, ExperimentSpec,
                                  run_experiment)
from repro.fed.flconfig import FLConfig


def default_spec() -> ExperimentSpec:
    """Tiny 4-client FCN experiment: fast enough for CI smoke runs."""
    return ExperimentSpec(
        name="quick-fcn",
        model=ComponentSpec("fcn"),
        data=ComponentSpec("mixture", {"n": 400, "n_eval": 200}),
        partition=ComponentSpec("label_skew", {"classes_per_client": 3}),
        fl=FLConfig(num_clients=4, tau=2, lr=0.05, batch_size=16,
                    use_lbgm=True, delta_threshold=0.2),
        rounds=10,
        eval=EvalPolicy(every=5, final=True, verbose=True),
    )


def parse_set(kvs) -> dict:
    """``["a.b=1", "c=x"]`` -> ``{"a.b": 1, "c": "x"}`` (JSON-ish values)."""
    out = {}
    for kv in kvs or ():
        if "=" not in kv:
            raise SystemExit(f"--set expects key=value, got {kv!r}")
        key, _, raw = kv.partition("=")
        try:
            out[key] = json.loads(raw)
        except json.JSONDecodeError:
            out[key] = raw
    return out


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.fed.run",
        description="Run one declarative FL experiment from a spec.")
    ap.add_argument("--spec", default=None,
                    help="path to an ExperimentSpec JSON file "
                         "(default: built-in quick-fcn spec)")
    ap.add_argument("--set", dest="sets", action="append", metavar="KEY=VAL",
                    help="dotted-key spec override, repeatable "
                         "(e.g. --set fl.delta_threshold=0.4)")
    ap.add_argument("--rounds", type=int, default=None,
                    help="override the spec's round count")
    ap.add_argument("--out", default=None,
                    help="write the full result (records + spec) as JSON")
    ap.add_argument("--print-spec", action="store_true",
                    help="print the resolved spec as JSON and exit")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the checkpoint at fl.ckpt_path "
                         "(requires fl.ckpt_every/fl.ckpt_path in the "
                         "spec); the completed history is bit-for-bit "
                         "the uninterrupted run's")
    args = ap.parse_args(argv)

    enable_compile_cache()
    spec = (ExperimentSpec.load(args.spec) if args.spec else default_spec())
    overrides = parse_set(args.sets)
    if overrides:
        spec = spec.with_overrides(overrides)
    if args.rounds is not None:
        spec = spec.with_overrides({"rounds": args.rounds})
    if args.print_spec:
        print(spec.to_json())
        return 0

    result = run_experiment(spec, resume=args.resume)
    last = result.records[-1]
    print(f"[{spec.name}] {result.rounds} rounds in "
          f"{result.duration_s:.2f}s "
          f"({result.us_per_round / 1e3:.1f} ms/round)")
    print(f"  loss={last.loss:.4f} frac_scalar={last.frac_scalar:.2f} "
          f"uplink={result.total_uplink:.3g} floats "
          f"savings={result.savings:.1%}")
    print(f"  wire={last.total_wire_bytes:.3g} bytes "
          f"(codec={spec.fl.codec}) wire_savings={last.wire_savings:.1%}")
    if result.final_eval:
        print("  " + " ".join(f"{k}={v:.4f}"
                              for k, v in sorted(result.final_eval.items())))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result.to_dict(), f, indent=2)
        print(f"  result written to {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
