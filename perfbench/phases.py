#!/usr/bin/env python3
"""Device time by phase of Algorithm 1 in one traced window of a cell.

    python3 perfbench/phases.py --workload qwen1.5-4b.ring-short --seed 7 \\
        --seconds 30

Runs the cell as ``run.py --trace 1`` does (``harness.run``), and keeps two
things that run makes and then drops: the round's compiled text, which it
compiles for its kernel names, and the loaded trace.  Prints, as the last
line of standard output, one JSON object: ``result``, the run's own result
object, and ``phases``, from the cell's first chip: each phase's device
seconds (``scopes.phase_table``), the device's busy seconds, the busy
seconds in no phase with their top ops by HLO name, the window's seconds,
and ``grad_flops_pct`` and ``flat_copy_pct`` over all the cell's chips
(``scopes.py``).  Exits 2 with no result where JAX finds no TPU or fewer
chips than the cell asks for.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from unittest import mock  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import counts, harness, scopes  # noqa: E402
from perfbench import trace as trace_lib  # noqa: E402
from perfbench.peaks import peaks_for  # noqa: E402
from perfbench.spec import load_cell  # noqa: E402


def traced_run(cell, *, seed: int, seconds: float, t_start: float):
    """``harness.run`` with the trace on; returns (result, compiled text
    of the round, trace)."""
    kept = {}
    kernel_names, load_trace = harness._kernel_names, trace_lib.load_trace

    def keep_text(text):
        kept["text"] = text
        return kernel_names(text)

    def keep_trace(log_dir):
        kept["trace"] = load_trace(log_dir)
        return kept["trace"]

    with mock.patch.object(harness, "_kernel_names", keep_text), \
            mock.patch.object(trace_lib, "load_trace", keep_trace):
        result = harness.run(cell, seed=seed, seconds=seconds, trace=True,
                             t_start=t_start)
    return result, kept["text"], kept["trace"]


def phase_report(cell, result: dict, text: str, trace) -> dict:
    """The per-phase table of one traced run."""
    tr = cell.traffic
    lo, hi = trace_lib.window_of(trace.spans)
    ids = sorted(trace.devices)[:cell.chips]
    ctx = {"trace": trace, "lo": lo, "hi": hi, "device_ids": ids,
           "scopes": scopes.hlo_scopes(text),
           "steps": result["attempted"] * tr["h"],
           "flops_per_step": counts.model_flops_per_token(
               cell.config["arch"], tr["seq_len"])
           * tr["agents"] * tr["per_agent_batch"] * tr["seq_len"],
           "peaks": peaks_for(result["device"]["kind"])}
    table = scopes.phase_table(trace.devices[ids[0]], ctx["scopes"], lo,
                               hi) if ids else {}
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
    result, text, trace = traced_run(cell, seed=args.seed,
                                     seconds=args.seconds, t_start=T_START)
    print(json.dumps({"result": result,
                      "phases": phase_report(cell, result, text, trace)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
