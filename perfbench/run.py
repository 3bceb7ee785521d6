#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload qwen1.5-4b.ring-short --seed 7 \\
        --seconds 20 --trace 0

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted`` and ``failed`` rounds, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device`` and, last,
``checks``: each number compared with the reference beside its limit.  The
same numbers end standard error.  Exits 2 with no result where JAX finds no
TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import harness  # noqa: E402
from perfbench.spec import load_cell  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = load_cell(args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"perfbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
