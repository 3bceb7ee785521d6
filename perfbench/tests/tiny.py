"""Tiny versions of the benchmark's cells, for tests on the CPU.

Same files, same paths through the harness; only the widths and the
sequence are cut so that a test run can hold them.  The limits are this
size's own: at d_model 64 a loss or a change sums far fewer bfloat16
products than at the published widths, so sound runs read higher.  Read
at this size on the CPU, over 8 seeds: sound runs of the program up to
4.2e-4 (loss_gap), 6.0e-3 (change_gap) and 1.3e-2 (spread_gap); the
float8 control from 3.5e-3, 3.4e-2 and 5.3e-2; the faults planted in the
reference from 1.2e-3, 0.12 and 0.66.
"""

from __future__ import annotations

import dataclasses

from perfbench.spec import load_cell

TINY_LIMITS = {"loss_gap": 8e-4, "change_gap": 1.2e-2, "spread_gap": 2.6e-2}
TINY_ARCH = {"d_model": 64, "num_heads": 4, "num_kv_heads": 4,
             "head_dim": 16, "d_ff": 128, "vocab_size": 256}


def tiny_cell(workload: str):
    """The cell, cut."""
    cell = load_cell(workload)
    arch = dict(cell.config["arch"], **TINY_ARCH)
    return dataclasses.replace(
        cell, config=dict(cell.config, arch=arch), limits=TINY_LIMITS,
        traffic=dict(cell.traffic, seq_len=32))
