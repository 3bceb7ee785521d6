"""The numbers that decide ``correct``, each against its limit.

The round the window drives is one compiled call of H steps, and set-up
drives it once, from the seed, before the window: those H steps are the
ones compared.  The reference (``algorithm1.py``) follows them from the
same weights, tokens and key.  Three numbers are compared:

* ``loss_gap``    the largest relative gap, over the H steps, between the
  program's mean loss over the agents and the reference's;
* ``change_gap``  the worst segment: for every agent and every segment of
  its parameters (a leaf, split by layer), the gap between the norm of the
  program's change over the round and the norm of the reference's,
  divided by the larger of the reference's norm of that segment and the
  median segment's.  Segments whose step-1 reference gradient is under a
  thousandth of the median segment's are left out: they move by round-off
  alone;
* ``spread_gap``  the same, of each agent's deviation from the agents'
  mean at the end of the round, measured against the reference's
  deviation.  The server round sets every agent to one average one step
  before the round ends, so what sets the agents apart is the last step's
  gradients mixed by W: this number is the mix's own.

The change covers the gradients, the update, the gossip mix with W and the
server average of the K sampled agents.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.weights import leaf_paths

__all__ = ["segments", "tree_segment_norms", "flat_norms", "numbers",
           "judge", "GRAD_FLOOR"]

GRAD_FLOOR = 1e-3


def segments(shapes) -> list[tuple[str, int, int | None, int]]:
    """(name, leaf index, layer or None, size) of each compared segment;
    leaves stacked for the program's scan split along their layer dim."""
    out = []
    for i, (path, leaf) in enumerate(zip(leaf_paths(shapes),
                                         jax.tree.leaves(shapes))):
        size = math.prod(leaf.shape)
        if "/scan/" in f"/{path}":
            layers = leaf.shape[0]
            out += [(f"{path}[{g}]", i, g, size // layers)
                    for g in range(layers)]
        else:
            out.append((path, i, None, size))
    return out


def tree_segment_norms(segs, tree) -> jax.Array:
    """(segments,) float32 norms of one agent's tree."""
    leaves = jax.tree.leaves(tree)
    return jnp.stack([
        jnp.linalg.norm((leaves[i] if g is None else leaves[i][g])
                        .reshape(-1).astype(jnp.float32))
        for _, i, g, _ in segs])


@partial(jax.jit, static_argnums=(0, 1))
def _flat_norms(segs, offsets, flat, params0):
    leaves = jax.tree.leaves(params0)
    change, spread = [], []
    for _, i, g, size in segs:
        start = offsets[i] + (0 if g is None else g * size)
        row0 = (leaves[i] if g is None else leaves[i][g]).reshape(-1)
        part = flat[:, start:start + size].astype(jnp.float32)
        change.append(jnp.linalg.norm(part - row0[None], axis=1))
        spread.append(jnp.linalg.norm(part - part.mean(0), axis=1))
    return jnp.stack(change, axis=1), jnp.stack(spread, axis=1)


def flat_norms(segs, spec, flat, params0) -> tuple[np.ndarray, np.ndarray]:
    """(n, segments) norms of each agent's change from ``params0`` and of
    its deviation from the agents' mean, read from the program's (n, D)
    buffer through its layout."""
    change, spread = _flat_norms(tuple(segs), tuple(spec.offsets), flat,
                                 params0)
    return np.asarray(change, np.float64), np.asarray(spread, np.float64)


def _worst_gap(prog, ref, keep) -> float:
    r = ref[:, keep]
    p = np.asarray(prog, np.float64)[:, keep]
    if not np.all(np.isfinite(p)):
        return math.inf
    return float(np.max(np.abs(p - r) / np.maximum(r, np.median(r))))


def numbers(losses, change, spread, ref) -> dict:
    """The compared numbers of a program round against a ReferenceRound,
    and how many segments the gradient floor left out."""
    losses = np.asarray(losses, np.float64)
    loss_gap = float(np.max(np.abs(losses - ref.losses)
                            / np.abs(ref.losses)))
    if not np.all(np.isfinite(losses)):
        loss_gap = math.inf
    keep = ref.grad1 >= GRAD_FLOOR * np.median(ref.grad1)
    return {"loss_gap": loss_gap,
            "change_gap": _worst_gap(change, ref.change, keep),
            "spread_gap": _worst_gap(spread, ref.spread, keep),
            "segments_left_out": int((~keep).sum())}


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: {'value', 'limit'}}): every number at or under
    its limit."""
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
