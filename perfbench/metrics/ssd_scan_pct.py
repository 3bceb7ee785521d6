"""Share of the traced window in which the chips run the chunked SSD scan
of the Mamba-2 layers, averaged over the chips.  The scan's ops are the
instructions of the round's compiled text whose ``op_name`` carries the
scope ``ssm.ssd`` (``models/ssm.py``: the forward, its recomputation and
its backward); a fusion carries the ``op_name`` of its root.  Their device
time is the union of their intervals in the window.  Nothing is read where
the round has no such op."""

import re

from perfbench import trace as trace_lib

SCOPE = "ssm.ssd"

_SCOPED = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*op_name="[^"]*'
                     + r"(?<![\w.])" + re.escape(SCOPE) + r"(?![\w.])",
                     re.M)


def scan_ops(compiled_text: str) -> set:
    """HLO names of the instructions in the SSD scan's scope."""
    return set(_SCOPED.findall(compiled_text))


def read(ctx):
    names = scan_ops(ctx.get("text", ""))
    if not names or not ctx["device_ids"]:
        return None
    ns = [trace_lib.busy_ns([o for o in ctx["trace"].devices[i]
                             if o.hlo_op in names], ctx["lo"], ctx["hi"])
          for i in ctx["device_ids"]]
    return 100.0 * sum(ns) / len(ns) / (ctx["hi"] - ctx["lo"])
