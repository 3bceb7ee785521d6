"""Plain reference models, one file each, named by a configuration's
``reference`` key.  Each file defines

* ``loss(params, tokens, arch, mm)``: the mean next-token cross entropy of
  ``tokens`` under the parameter tree ``params``, every product through
  ``mm``, which the caller sets to a precision;
* ``flops_per_token(arch, seq)``: the operations the forward and backward
  passes of that model require per training token at sequence length
  ``seq``, recomputation not counted: what ``mfu_pct`` and
  ``grad_flops_pct`` count as the step's work.

``arch`` is the configuration file's ``arch`` object, as JSON gives it.
"""

from __future__ import annotations

import re

__all__ = ["layer_params"]


def layer_params(stack: dict) -> list[dict]:
    """The stack's per-layer parameter dicts, in layer order.

    The program keeps leading layers as ``pre_<i>``, a repeating unit of
    ``period`` layers stacked along a leading dim under
    ``scan/sub_<j>``, and trailing layers as ``suf_<i>``.
    """
    def numbered(prefix):
        keys = [k for k in stack if re.fullmatch(f"{prefix}_\\d+", k)]
        return [stack[k] for k in sorted(keys, key=lambda k: int(k[4:]))]

    layers = numbered("pre")
    if "scan" in stack:
        subs = [stack["scan"][f"sub_{j}"] for j in range(len(stack["scan"]))]
        groups = next(iter(_leaves(subs[0]))).shape[0]
        for g in range(groups):
            layers += [_index(sub, g) for sub in subs]
    return layers + numbered("suf")


def _leaves(tree):
    for v in tree.values():
        if isinstance(v, dict):
            yield from _leaves(v)
        else:
            yield v


def _index(tree: dict, g: int) -> dict:
    return {k: _index(v, g) if isinstance(v, dict) else v[g]
            for k, v in tree.items()}
