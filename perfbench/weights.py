"""Model weights made by the benchmark from the seed, in one jitted call.

The weights take the program's parameter tree (its structure and shapes,
from ``jax.eval_shape`` of the model's init), but their values come from
here: the reference reads the same tree, and nothing the program computes
enters it.  Each leaf is drawn from its own fold of the key, so the values
do not depend on where the leaf is placed.  A leaf's rule is found by its
name (the last key of its path), the names the program's models give
their leaves; a name with no rule is an error.  Layers stacked for the
program's scan carry a leading layer dim, which no rule counts.

Scales:

* ``w``, a matrix: N(0, 1/fan_in), truncated at 2σ, as the program's
  ``layers.init_dense``.  The fan-in is the product of every dim but the
  last, except where the module's input is one dim of its weight
  (``_FAN_IN``): the q/k/v projections (d, heads, head_dim) and MLA's
  up-projections (rank, heads, head_dim) take dim 0; stacked experts
  (E, d_in, d_out) take dim 1 (DeepSeek-V2, arXiv:2405.04434, and the
  program's ``moe.init_moe``: each expert is its own d_in -> d_out
  matrix);
* ``table``, the embedding: N(0, 0.02²);
* ``scale`` and ``b``, RMSNorm offsets (the program's ``1 + scale``) and
  biases: N(0, 0.02²), small random values so that they are exercised;
* Mamba2's mixer, the defaults of the published ``mamba_ssm`` ``Mamba2``
  module (arXiv:2405.21060, github.com/state-spaces/mamba,
  ``mamba_ssm/modules/mamba2.py``):
  ``a_log`` = log U(1, 16) (``A_init_range``); ``dt_bias`` the inverse
  softplus, dt + log(-expm1(-dt)), of dt = exp U(log 1e-3, log 1e-1)
  floored at 1e-4 (``dt_min``, ``dt_max``, ``dt_init_floor``);
  ``d_skip`` 1 + N(0, 0.02²), near the published ones and exercised;
* ``conv_w`` and ``conv_b``, the depthwise causal convolutions of Mamba2
  and of Griffin's recurrent block: U(±1/√k) with k the kernel width
  (``conv_w``'s first dim), PyTorch's ``Conv1d`` default for a depthwise
  kernel (fan-in k), which ``mamba_ssm`` keeps;
* ``lam``, RG-LRU's Λ (Griffin, arXiv:2402.19427, section 2.4): the decay
  at a full recurrence gate, a^c with c = 8, is U(0.9, 0.999).  The
  program writes that decay exp(-c·softplus(Λ)), i.e. σ(-Λ)^c (the
  paper's σ(Λ)^c with Λ negated), so Λ = softplus⁻¹(-log(u) / c).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["leaf_paths", "make_weights"]

# Griffin's fixed gate sharpness c
RG_LRU_C = 8.0

# index of the fan-in dim of a weight whose input is one of its dims, by
# its module's name, or "<parent>/<name>" where the name alone is shared
_FAN_IN = {
    "wq": 0, "wk": 0, "wv": 0,              # (d, heads, head_dim)
    "wq_b": 0, "wk_b": 0, "wv_b": 0,        # MLA (rank, heads, head_dim)
    "moe/wi": 1, "moe/wg": 1, "moe/wo": 1,  # experts (E, d_in, d_out)
}


def leaf_paths(tree) -> list[str]:
    """'/'-joined dict keys of every leaf, in ``jax.tree.leaves`` order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def _fan_in(path: str, shape: tuple) -> int:
    parts = path.split("/")
    for name in ("/".join(parts[-3:-1]), parts[-2]):
        if name in _FAN_IN:
            return shape[_FAN_IN[name]]
    return math.prod(shape[:-1])


def _core(path: str, shape: tuple) -> tuple:
    """The shape without the leading layer dim of a leaf stacked for the
    program's scan."""
    return tuple(shape[1:] if "/scan/" in f"/{path}" else shape)


def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, minval=lo, maxval=hi)


def _inverse_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


def _leaf(key, path: str, shape: tuple, dtype, siblings: dict):
    """One leaf's values; ``siblings``: {leaf name: shape without the
    layer dim} of the leaves beside it."""
    name = path.split("/")[-1]
    if name == "w":
        std = 1.0 / math.sqrt(_fan_in(path, _core(path, shape)))
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape) * std
    elif name == "table":
        x = jax.random.normal(key, shape) * 0.02
    elif name in ("scale", "b"):
        x = jax.random.normal(key, shape) * 0.02
    elif name == "a_log":
        x = jnp.log(_uniform(key, shape, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(_uniform(key, shape, math.log(1e-3), math.log(1e-1)))
        x = _inverse_softplus(jnp.maximum(dt, 1e-4))
    elif name == "d_skip":
        x = 1.0 + jax.random.normal(key, shape) * 0.02
    elif name in ("conv_w", "conv_b"):
        bound = 1.0 / math.sqrt(siblings["conv_w"][0])
        x = _uniform(key, shape, -bound, bound)
    elif name == "lam":
        u = _uniform(key, shape, 0.9, 0.999)
        x = _inverse_softplus(-jnp.log(u) / RG_LRU_C)
    else:
        raise ValueError(f"no initialisation rule for leaf {path!r}")
    return x.astype(dtype)


def make_weights(key: jax.Array, shapes):
    """The parameter tree of ``shapes`` (a tree of ShapeDtypeStructs) with
    values drawn from ``key``, made on the device in one jitted call."""
    leaves, treedef = jax.tree.flatten(shapes)
    paths = leaf_paths(shapes)
    siblings: dict[str, dict] = {}
    for p, leaf in zip(paths, leaves):
        parent, _, name = p.rpartition("/")
        siblings.setdefault(parent, {})[name] = _core(p, leaf.shape)

    def make(k):
        return jax.tree.unflatten(treedef, [
            _leaf(jax.random.fold_in(k, i), p, tuple(leaf.shape),
                  leaf.dtype, siblings[p.rpartition("/")[0]])
            for i, (p, leaf) in enumerate(zip(paths, leaves))])

    return jax.jit(make)(key)
