# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows. Figures covered: 1 (PCA), 5 (standalone), 6 (threshold),
# 7 (plug-and-play), 8 (SignSGD distributed).
#
# Run from the repo root as a module (the package __init__ bootstraps the
# src/ path, same convention as pytest.ini's ``pythonpath = src``):
#
#     python -m benchmarks.run --only fig5
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,fig5,fig6,fig7,fig8,cohort,"
                         "robustness,wire_bytes,async")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--toy", action="store_true",
                    help="tiny problem sizes (CI smoke): small cohorts, "
                         "narrow model — exercises "
                         "every code path incl. the BENCH_engine.json "
                         "trajectory, makes no perf claims")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    want = set(args.only.split(",")) if args.only else None

    def on(name):
        return want is None or name in want

    print("name,us_per_call,derived")
    if on("fig1"):
        from benchmarks import fig1_pca
        fig1_pca.run(epochs=25)
    if on("fig5"):
        from benchmarks import fig5_standalone
        fig5_standalone.run(rounds=args.rounds)
    if on("fig6"):
        from benchmarks import fig6_threshold
        fig6_threshold.run(rounds=args.rounds)
    if on("fig7"):
        from benchmarks import fig7_plugplay
        fig7_plugplay.run(rounds=args.rounds)
    if on("fig8"):
        from benchmarks import fig8_signsgd
        fig8_signsgd.run(rounds=args.rounds)
    if on("cohort"):
        from benchmarks import cohort_scaling
        if args.toy:
            cohort_scaling.run(rounds=2, cohorts=(8,), chunk_size=4,
                               scalar_cohorts=(8,), scalar_rounds=2,
                               scalar_warmup=2, scalar_d_model=64,
                               mesh_cohorts=(8,), host_cohorts=(16,),
                               tier_levels=(4, 2))
        else:
            cohort_scaling.run(rounds=min(args.rounds, 5))
    if on("robustness"):
        from benchmarks import robustness
        if args.toy:
            robustness.run(rounds=3, num_clients=8, n_data=320,
                           fracs=(0.25,),
                           attacks=(("sign_flip", {"scale": 4.0}),),
                           headline_frac=0.25)
        else:
            robustness.run(rounds=args.rounds)
    if on("wire_bytes"):
        from benchmarks import wire_bytes
        if args.toy:
            wire_bytes.run(rounds=3, num_clients=8, n_data=320)
        else:
            wire_bytes.run(rounds=args.rounds)
    if on("async"):
        from benchmarks import async_heterogeneity
        if args.toy:
            async_heterogeneity.run(rounds=4, num_clients=8, n_data=320,
                                    fracs=(0.25,), delays=(2,),
                                    aggs=("mean",), headline_frac=0.25)
        else:
            async_heterogeneity.run(rounds=max(args.rounds, 40))


if __name__ == '__main__':
    main()
