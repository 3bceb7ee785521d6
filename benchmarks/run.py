"""Benchmark entry point — one experiment per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus '#'-prefixed claim-check
commentary) and writes full curves/tables under results/benchmarks/.

  fig4_convergence — Fig. 4: FedDec vs FedAvg, 2 graphs × H∈{10,100}
  table1_lambda2   — Table 1: |λ₂|² across graph families
  fig2_alpha       — Fig. 2: α(|λ̂₂|) + Lemma 3 contraction check
  theory_check     — Theorem 1 bound vs measured trajectory
  ablation_server  — beyond-paper: §5 conjecture (server vs pure gossip)
"""

import argparse


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="reduced T/seeds for CI")
    p.add_argument("--only", default=None)
    args = p.parse_args()

    from benchmarks import (ablation_server, fig2_alpha, fig4_convergence,
                            table1_lambda2, theory_check)
    jobs = {
        "table1_lambda2": lambda: table1_lambda2.main(
            seeds=3 if args.quick else 10),
        "fig2_alpha": fig2_alpha.main,
        "fig4_convergence": lambda: fig4_convergence.main(
            t_steps=1500 if args.quick else 5000,
            seeds=3 if args.quick else 10),
        "theory_check": theory_check.main,
        "ablation_server": lambda: ablation_server.main(
            t_steps=1500 if args.quick else 3000,
            seeds=3 if args.quick else 6),
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
