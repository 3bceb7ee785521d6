#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 perfbench/control.py --workload qwen1.5-4b.ring-short \\
        --seeds 11,12,13 --faults half_batch,no_mix,one_edge

For each seed, in one process and with no timed window:

* ``program``  the checked round of the program as the benchmark drives
  it, against the float32 reference: the lower readings;
* ``control``  the reference itself put in the program's place, computed
  with float8 (e4m3, one scale per tensor) operands in every product, the
  precision step below the configuration's bfloat16: the upper readings;
* each fault of ``faults.py`` named in ``--faults``, planted in the
  reference put in the program's place, against the same reference.

The control and the faults run on the first three seeds.

Prints one JSON line per (seed, kind) and, last, the largest program
reading and the smallest control and fault readings of each number.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import compare, faults, harness  # noqa: E402
from perfbench.spec import Cell, load_cell  # noqa: E402

__all__ = ["readings"]


def readings(cell: Cell, seeds, fault_names=(), control_seeds=3,
             emit=print) -> dict:
    """{kind: [numbers of each seed]} for 'program' (every seed), and for
    'control' and each fault (the first ``control_seeds`` seeds)."""
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = {"program": [], "control": [], **{f: [] for f in fault_names}}
    b = harness._build(cell)
    for i, seed in enumerate(seeds):
        s = harness._start(cell, b, seed)
        state, losses, change, spread = harness.checked_round(b, s)
        del state
        ref = harness.reference(cell, b, s)
        rows = [("program", losses, change, spread)]
        if i < control_seeds:
            ctrl = harness.reference(cell, b, s, mm="fp8")
            rows.append(("control", ctrl.losses, ctrl.change, ctrl.spread))
            for name in fault_names:
                bad = harness.reference(cell, b, s, fault=name)
                rows.append((name, bad.losses, bad.change, bad.spread))
        for kind, l, c, sp in rows:
            nums = compare.numbers(l, c, sp, ref)
            out[kind].append(nums)
            emit(json.dumps({"seed": seed, "kind": kind, **nums}))
    return out


def summary(out: dict) -> dict:
    """Largest program reading; smallest control and fault readings."""
    res = {}
    for kind, rows in out.items():
        pick = max if kind == "program" else min
        res[kind] = {k: pick(r[k] for r in rows) for k in rows[0]}
    return res


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated seeds")
    p.add_argument("--faults", default="",
                   help=f"comma-separated, of {','.join(faults.FAULTS)}")
    args = p.parse_args(argv)
    cell = load_cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print(f"control: {args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    out = readings(cell, [int(s) for s in args.seeds.split(",")],
                   [f for f in args.faults.split(",") if f])
    print(json.dumps({"summary": summary(out)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
