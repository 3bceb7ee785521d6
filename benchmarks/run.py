"""Benchmark entry point — one experiment per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus '#'-prefixed claim-check
commentary) and writes full curves/tables under results/benchmarks/.

  fig4_convergence — Fig. 4: FedDec vs FedAvg, 2 graphs × H∈{10,100}
  table1_lambda2   — Table 1: |λ₂|² across graph families
  fig2_alpha       — Fig. 2: α(|λ̂₂|) + Lemma 3 contraction check
  theory_check     — Theorem 1 bound vs measured trajectory
  bench_kernels    — kernel micro-benchmarks + Pallas validation
  bench_fused      — fused lax.scan round executor vs per-step dispatch
  bench_gossip     — gossip impls (dense/pallas/sparse × tree/flat layout)
  bench_sharded    — agent-sharded flat engine weak-scaling (shard_map
                     psum_scatter vs ppermute halo, 1–8 host devices)
  bench_compress   — compressed gossip (EF codecs, compressed halo bytes,
                     fused quant/dequant-mix kernels, linreg convergence)
  bench_sweep      — batched sweep engine vs the per-seed Python loop
                     (one-compile lattice execution at fig4 shapes)
  bench_population — cohort-sampled population engine (n_total up to 1e6:
                     flat peak-device bytes, streaming overlap, cohort
                     bit-identity vs the flat sparse engine)
  bench_delta      — delta-parameterized state (DeltaStore bytes vs the
                     dense store, rank=full bit-identity, batched
                     personalized serving vs the naive per-agent loop)
  bench_roundfuse  — fused update+gossip round (kernels/update_mix.py):
                     buffer-pass bytes + wall-clock fused vs unfused at
                     fig4 and n=1024, D=2^20, sharded boundary-halo
                     overlap rows
  ablation_server  — beyond-paper: §5 conjecture (server vs pure gossip)
  roofline         — aggregates results/dryrun into the §Roofline table
"""

import argparse


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="reduced T/seeds for CI")
    p.add_argument("--only", default=None)
    args = p.parse_args()

    from benchmarks import (ablation_server, bench_compress, bench_delta,
                            bench_fused, bench_gossip, bench_kernels,
                            bench_population, bench_roundfuse, bench_sharded,
                            bench_sweep, fig2_alpha, fig4_convergence,
                            roofline, table1_lambda2, theory_check)
    jobs = {
        "table1_lambda2": lambda: table1_lambda2.main(
            seeds=3 if args.quick else 10),
        "fig2_alpha": fig2_alpha.main,
        "fig4_convergence": lambda: fig4_convergence.main(
            t_steps=1500 if args.quick else 5000,
            seeds=3 if args.quick else 10),
        "theory_check": theory_check.main,
        "bench_kernels": bench_kernels.main,
        "bench_fused": lambda: bench_fused.main(quick=args.quick),
        "bench_gossip": lambda: bench_gossip.main(smoke=args.quick),
        "bench_sharded": lambda: bench_sharded.main(smoke=args.quick),
        "bench_compress": lambda: bench_compress.main(smoke=args.quick),
        "bench_sweep": lambda: bench_sweep.main(smoke=args.quick),
        "bench_population": lambda: bench_population.main(smoke=args.quick),
        "bench_delta": lambda: bench_delta.main(smoke=args.quick),
        "bench_roundfuse": lambda: bench_roundfuse.main(smoke=args.quick),
        "ablation_server": lambda: ablation_server.main(
            t_steps=1500 if args.quick else 3000,
            seeds=3 if args.quick else 6),
        "roofline": roofline.main,
    }
    print("name,us_per_call,derived")
    failed = []
    for name, job in jobs.items():
        if args.only and args.only != name:
            continue
        try:
            job()
        except Exception as e:  # noqa: BLE001 — keep the suite running
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            failed.append(name)
    if failed:
        raise SystemExit(f"{len(failed)} benchmark job(s) failed: "
                         + ", ".join(failed))


if __name__ == "__main__":
    main()
