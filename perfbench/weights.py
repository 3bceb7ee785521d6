"""Model weights made by the benchmark from the seed, in one jitted call.

The weights take the program's parameter tree (its structure and shapes,
from ``jax.eval_shape`` of the model's init), but their values come from
here: the reference reads the same tree, and nothing the program computes
enters it.  Each leaf is drawn from its own fold of the key, so the values
do not depend on where the leaf is placed.

Scales: a matrix gets N(0, 1/fan_in) (truncated at 2σ); the embedding
table 0.02; RMSNorm offsets (the program's ``1 + scale``) and biases small
random values, so that they are exercised.
"""

from __future__ import annotations

import math

import jax

__all__ = ["leaf_paths", "make_weights"]


def leaf_paths(tree) -> list[str]:
    """'/'-joined dict keys of every leaf, in ``jax.tree.leaves`` order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def _fan_in(path: str, shape: tuple) -> int:
    name = path.split("/")[-2]
    if name in ("wq", "wk", "wv"):           # (d, heads, head_dim)
        return shape[0]
    return math.prod(shape[:-1])


def _leaf(key, path: str, shape: tuple, dtype):
    name = path.split("/")[-1]
    # layers stacked for the program's scan carry a leading layer dim
    core = shape[1:] if "/scan/" in f"/{path}" else shape
    if name == "w":
        std = 1.0 / math.sqrt(_fan_in(path, core))
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape) * std
    elif name == "table":
        x = jax.random.normal(key, shape) * 0.02
    elif name in ("scale", "b"):
        x = jax.random.normal(key, shape) * 0.02
    else:
        raise ValueError(f"no initialisation rule for leaf {path!r}")
    return x.astype(dtype)


def make_weights(key: jax.Array, shapes):
    """The parameter tree of ``shapes`` (a tree of ShapeDtypeStructs) with
    values drawn from ``key``, made on the device in one jitted call."""
    leaves, treedef = jax.tree.flatten(shapes)
    paths = leaf_paths(shapes)

    def make(k):
        return jax.tree.unflatten(treedef, [
            _leaf(jax.random.fold_in(k, i), p, tuple(l.shape), l.dtype)
            for i, (p, l) in enumerate(zip(paths, leaves))])

    return jax.jit(make)(key)
