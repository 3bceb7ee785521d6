"""Plain reference models, one file each, named by a configuration's
``reference`` key.  Each file defines ``loss(params, tokens, arch, mm)``."""

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
