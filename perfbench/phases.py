#!/usr/bin/env python3
"""Device time by phase of Algorithm 1 in one traced window of a cell.

    python3 perfbench/phases.py --workload qwen1.5-4b.ring-short --seed 7 \\
        --seconds 30

Runs the cell as ``run.py --trace 1`` does (``harness.run``), and keeps
the readers' context that run makes and then drops: the trace, and the
phase of each instruction of the round's compiled text (``scopes.py``).
Prints, as the last line of standard output, one JSON object: ``result``,
the run's own result object, and ``phases``, from the cell's first chip:
each phase's device seconds (``scopes.phase_table``), the device's busy
seconds, the busy seconds in no phase with their top ops by HLO name, the
window's seconds, and ``grad_flops_pct`` and ``flat_copy_pct`` over all
the cell's chips, as the run's readers of those names give them.  Exits 2
with no result where JAX finds no TPU or fewer chips than the cell asks
for.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import harness, scopes  # noqa: E402
from perfbench.spec import load_cell  # noqa: E402


def traced_run(cell, *, seed: int, seconds: float, t_start: float):
    """``harness.run`` with the trace on; returns (result, the readers'
    context)."""
    ctx = {}
    result = harness.run(cell, seed=seed, seconds=seconds, trace=True,
                         t_start=t_start, keep_ctx=ctx)
    return result, ctx


def phase_report(ctx: dict) -> dict:
    """The per-phase table of one traced run's readers' context."""
    lo, hi, ids = ctx["lo"], ctx["hi"], ctx["device_ids"]
    table = scopes.phase_table(ctx["trace"].devices[ids[0]], ctx["scopes"],
                               lo, hi) if ids else {}
    return dict(table, window_s=(hi - lo) / 1e9,
                grad_flops_pct=scopes.grad_flops_pct(ctx),
                flat_copy_pct=scopes.flat_copy_pct(ctx))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    cell = load_cell(args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"perfbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result, ctx = traced_run(cell, seed=args.seed, seconds=args.seconds,
                             t_start=T_START)
    print(json.dumps({"result": result, "phases": phase_report(ctx)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
