"""Sharding rules: map every parameter leaf to a PartitionSpec.

Two federated layouts (DESIGN §3):

* ``sharded``    — n_agents == |agent axes| (16 single-pod, 32 multi-pod).
  Each leaf is (agents, [groups], *dims): agents over ('data',) /
  ('pod','data'), tensor-parallel dim over 'model'.  Gossip crosses the
  agent axes; an agent's compute stays on its 1×16 model slice.

* ``replicated`` — n_agents small (4); the agent dim is UNSHARDED and every
  agent's parameters are FSDP-sharded over the data axes + tensor-parallel
  over 'model'.  Used by the >100B archs where a per-agent replica cannot
  fit an HBM slice.  Gossip is then device-local (no collectives) — the
  cross-silo regime.

Name-based TP rules pick the canonical Megatron dims (column-parallel wi/wq,
row-parallel wo); anything unmatched falls back to "largest divisible dim".
All rules are *hints*: XLA SPMD inserts whatever collectives the annotations
imply, and §Perf iterates on them.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = ["MeshAxes", "param_pspecs", "n_agents_for"]


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Role assignment for the production mesh's axes."""

    data_axes: tuple[str, ...]   # ('data',) or ('pod', 'data')
    model_axis: str              # 'model'
    sizes: dict[str, int]

    @property
    def data_size(self) -> int:
        return int(np.prod([self.sizes[a] for a in self.data_axes]))

    @property
    def model_size(self) -> int:
        return self.sizes[self.model_axis]


def n_agents_for(cfg, axes: MeshAxes) -> int:
    """Agent count implied by (arch layout × mesh).

    ``replicated`` counts are PER POD (cross-silo: a pod is a silo, so the
    multi-pod mesh doubles the agent population).
    """
    if cfg.fed_agent_layout == "replicated":
        return cfg.fed_n_agents_replicated * axes.sizes.get("pod", 1)
    return axes.data_size


# ---------------------------------------------------------------------------
# generic divisibility-aware axis assignment
# ---------------------------------------------------------------------------


def _assign(shape: tuple[int, ...],
            preferences: list[tuple[int, Any]],
            fallback_axes: list[Any] = ()) -> P:
    """Build a PartitionSpec trying (dim, axis-or-axes) preferences in order.

    An assignment is taken only if the dim size is divisible by the axis
    (product) size and neither dim nor axis is already used.  ``fallback_axes``
    are then greedily assigned to the largest unused divisible dim.
    """
    spec: list[Any] = [None] * len(shape)
    used_axes: set[str] = set()

    def axis_size(ax) -> int:
        return int(np.prod([_SIZES[a] for a in (ax if isinstance(ax, tuple)
                                                 else (ax,))]))

    def axis_names(ax):
        return ax if isinstance(ax, tuple) else (ax,)

    def try_assign(dim, ax) -> bool:
        if dim >= len(shape) or spec[dim] is not None:
            return False
        if any(a in used_axes for a in axis_names(ax)):
            return False
        if shape[dim] % axis_size(ax):
            return False
        spec[dim] = ax
        used_axes.update(axis_names(ax))
        return True

    for dim, ax in preferences:
        try_assign(dim, ax)
    for ax in fallback_axes:
        dims = sorted(range(len(shape)), key=lambda d: -shape[d])
        for dim in dims:
            if try_assign(dim, ax):
                break
    return P(*spec)


_SIZES: dict[str, int] = {}


def _with_sizes(axes: MeshAxes):
    global _SIZES
    _SIZES = dict(axes.sizes)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

# (path-suffix match, preferred (dim, axis) list) — dims are indices into the
# *parameter's own* shape (agent/group dims handled by the caller).
# Returns (preferences, allow_fallback): fallback=False pins unmatched
# params to replication (e.g. GQA kv weights when kv_heads < tp — Megatron
# replicates small KV heads rather than partial-summing activations).
def _tp_preferences(path: tuple[str, ...], shape: tuple[int, ...],
                    model: str, cfg) -> tuple[list[tuple[int, Any]], bool]:
    names = [getattr(p, "key", str(p)) for p in path]
    leaf = names[-1]
    parent = names[-2] if len(names) > 1 else ""
    tp = _SIZES.get(model, 1)

    def is_under(*keys):
        return any(k in names for k in keys)

    # embeddings / head ------------------------------------------------------
    if leaf == "table":                      # (vocab, d)
        return [(0, model), (1, model)], True
    if parent == "head":                     # w: (d, vocab)
        return [(1, model), (0, model)], True
    # attention --------------------------------------------------------------
    if parent in ("wk", "wv") and len(shape) == 3:  # (d, KV, hd)
        if cfg is not None and cfg.num_kv_heads % tp == 0:
            return [(1, model)], False
        if cfg is not None and cfg.num_kv_heads < cfg.num_heads:
            return [], False                 # GQA: replicate small KV
        return [(0, model)], False           # MHA: d-shard (+weight gather)
    if parent in ("wq", "wq_b", "wk_b", "wv_b"):
        return [(1, model), (0, model)], False  # (d|rank, H, hd) → heads
    if parent == "wo" and len(shape) == 3:   # (H, hd, d)
        return [(0, model), (2, model)], False  # row-par., else column on d
    if parent in ("wq_a", "wkv_a"):          # (d, rank) — small, replicate
        return [], False
    # mlp ---------------------------------------------------------------------
    if parent in ("wi", "wg") and len(shape) == 2:
        return [(1, model)], False           # column-parallel (d, ff)
    if parent == "wo" and len(shape) == 2:
        return [(0, model)], False           # row-parallel (ff, d)
    # moe ---------------------------------------------------------------------
    if len(shape) == 3 and parent in ("wi", "wg", "wo"):
        return [(0, model)], False           # (E, d, f) expert-parallel
    if parent == "router":                   # (d, E)
        return [(1, model)], False
    # ssm ---------------------------------------------------------------------
    if parent == "in_proj":                  # (d, 2di+2n+nh)
        return [(1, model)], False
    if parent == "out_proj":                 # (di|W, d)
        return [(0, model), (1, model)], False
    if leaf in ("conv_w",):                  # (K, C)
        return [(1, model)], False
    # rglru -------------------------------------------------------------------
    if parent in ("proj_gelu", "proj_rec"):  # (d, W)
        return [(1, model)], False
    if parent in ("w_a", "w_x"):             # (W, W) diagonal-ish gates
        return [(1, model)], False
    # fallback: largest divisible dim over model
    if len(shape) >= 2:
        dims = sorted(range(len(shape)), key=lambda d: -shape[d])
        return [(d, model) for d in dims], True
    return [], False


def param_pspecs(cfg, params_tree: Any, axes: MeshAxes) -> Any:
    """PartitionSpec pytree for *stacked* federated params.

    ``params_tree`` leaves are (agents, [groups], *param_dims) — produced by
    feddec.init_state over model.init (the caller tells us nothing else;
    group dims are recognised by path prefix 'scan').
    """
    _with_sizes(axes)
    layout = cfg.fed_agent_layout
    model = axes.model_axis

    def rule(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        shape = tuple(leaf.shape)
        lead = 1  # agent dim
        if "scan" in names:
            lead += 1  # group dim
        inner_shape = shape[lead:]
        tp_prefs, _ = _tp_preferences(path, inner_shape, model, cfg)
        prefs = [(d + lead, ax) for d, ax in tp_prefs]
        if layout == "sharded":
            agent_ax = axes.data_axes if len(axes.data_axes) > 1 \
                else axes.data_axes[0]
            spec = _assign(shape, [(0, agent_ax)] + prefs)
        else:
            # agent dim unsharded; FSDP over data axes on the largest dim
            fsdp_ax = axes.data_axes if len(axes.data_axes) > 1 \
                else axes.data_axes[0]
            spec = _assign(shape, prefs, fallback_axes=[fsdp_ax])
            # never let FSDP land on the agent dim
            if spec[0] is not None:
                spec = P(None, *spec[1:])
        return spec

    return jax.tree_util.tree_map_with_path(rule, params_tree)


def serve_param_pspecs(cfg, params_tree: Any, axes: MeshAxes) -> Any:
    """Specs for *unstacked* serving params: TP over model, FSDP over data."""
    _with_sizes(axes)
    model = axes.model_axis
    fsdp_ax = axes.data_axes if len(axes.data_axes) > 1 else axes.data_axes[0]

    def rule(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        shape = tuple(leaf.shape)
        lead = 1 if "scan" in names else 0
        inner_shape = shape[lead:]
        tp_prefs, _ = _tp_preferences(path, inner_shape, model, cfg)
        prefs = [(d + lead, ax) for d, ax in tp_prefs]
        return _assign(shape, prefs, fallback_axes=[fsdp_ax])

    return jax.tree_util.tree_map_with_path(rule, params_tree)
