"""The gossip averaging step  x_i ← Σ_j W_ij x_j  (Algorithm 1, line 6).

Four execution paths, identical math, different cost models:

1. ``gossip_mix_dense`` — ``einsum('ij,j...->i...')`` on stacked parameters.
   Under pjit/SPMD with the agent dim sharded, XLA lowers this to an
   all-gather of every agent's parameters (O(n·d) bytes per agent).  Simple,
   fully general (any W), and the **baseline** for the roofline.

2. ``gossip_mix_permute`` — a ``shard_map`` schedule of
   ``jax.lax.ppermute`` rounds covering only the graph's edges
   (O(deg·d) bytes per agent).  This is the TPU-native realisation of
   "agents talk to neighbours only" and the §Perf optimized path.

3. ``kernels.ops.gossip_mix`` — a Pallas kernel for the local
   (n, n) @ (n, D) mixing contraction once parameters are resident
   (the flat-engine ``gossip_impl='pallas'`` hot path; see
   kernels/gossip_mix.py and repro/core/flat.py).

4. ``make_sparse_gossip`` — neighbour-only gather + ``segment_sum`` over the
   graph's static CSR edge list (:func:`repro.core.topology.csr_edges`):
   O(|E|·d) instead of the dense O(n²·d), which is what lets ``n_agents``
   scale past the dense contraction (``gossip_impl='sparse'``; Pallas
   edge-blocked variant in kernels/gossip_mix.py).

All paths preserve the mean exactly when W is doubly stochastic — the
invariant Lemma 2 relies on (x̄^{t+1} = x̄^{t+1/2}); tests/test_gossip_server.py
and tests/test_gossip_impls.py check it property-style.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import topology as topo

__all__ = [
    "gossip_mix_dense",
    "gossip_mix_permute",
    "lattice_max_degree",
    "make_permute_gossip",
    "make_sparse_gossip",
    "make_sparse_gossip_batched",
    "make_sparse_gossip_tree",
    "stacked_ell_tables",
]


def gossip_mix_dense(w: jax.Array, stacked: object) -> object:
    """Apply  y_i = Σ_j W_ij x_j  to every leaf of a stacked pytree.

    Args:
      w: (n, n) mixing matrix.
      stacked: pytree whose leaves all have a leading agent dim of size n.
    """
    def mix(leaf: jax.Array) -> jax.Array:
        return jnp.einsum("ij,j...->i...", w.astype(leaf.dtype), leaf,
                          precision=jax.lax.Precision.HIGHEST)
    return jax.tree.map(mix, stacked)


ELL_MAX_DEG = 16  # below this, the padded neighbour loop beats CSR scatter


def make_sparse_gossip(graph: topo.Graph):
    """Neighbour-only gossip over the graph's static edge structure.

    ``y_i = W_ii x_i + Σ_{(i,j)∈E} W_ij x_j`` at O(|E|·d) (vs the dense
    contraction's O(n²·d)) — the mixing *support* is static (the graph),
    only the weights vary per step (link failures zero entries of the
    sampled W; a dead edge contributes 0, so no re-indexing is needed).
    Two realisations, picked by the graph's max degree:

    * **ELL** (max_deg ≤ %d): neighbour lists padded to (n, max_deg)
      (padding points at the row's own agent, weight 0); the mix is
      max_deg fused gather-multiply-add passes over (n, d) — no scatter,
      no (|E|, d) temporary.  The typical regime (rings, geometric
      graphs): the n/deg× FLOP cut over dense that makes n_agents ≳ 256
      sustainable.
    * **CSR** (skewed degrees): gather over the receiver-sorted edge list
      (:func:`repro.core.topology.csr_edges`) + ``segment_sum`` — work
      stays O(|E|·d) even when one hub has a huge degree.

    Returns:
      mix(w, x) -> y for stacked arrays x of shape (n, ...) — the flat
      engine's (n, D) buffer, or any single leaf.  For pytrees use
      :func:`make_sparse_gossip_tree`.
    """
    n = graph.n
    adj = np.asarray(graph.adjacency)
    max_deg = int(adj.sum(axis=1).max()) if n else 0

    def bcast(v, ndim):
        return v[(...,) + (None,) * (ndim - 1)]

    if max_deg == 0:  # isolated graph (FedAvg 𝒲 = {I}): y = W_ii x_i
        return lambda w, x: bcast(jnp.diagonal(w.astype(x.dtype)),
                                  x.ndim) * x

    if max_deg <= ELL_MAX_DEG:
        nbr = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, max_deg))
        pad = np.zeros((n, max_deg), dtype=bool)
        for i in range(n):
            js = np.flatnonzero(adj[i])
            nbr[i, :len(js)] = js
            pad[i, len(js):] = True
        nbr_j = jnp.asarray(nbr)
        pad_j = jnp.asarray(pad)

        def mix(w: jax.Array, x: jax.Array) -> jax.Array:
            wd = w.astype(x.dtype)
            wv = jnp.where(pad_j, 0,
                           jnp.take_along_axis(wd, nbr_j, axis=1))
            y = bcast(jnp.diagonal(wd), x.ndim) * x
            for k in range(max_deg):
                y = y + bcast(wv[:, k], x.ndim) \
                    * jnp.take(x, nbr_j[:, k], axis=0)
            return y

        return mix

    recv, send, _ = topo.csr_edges(graph)
    recv_idx = jnp.asarray(recv)
    send_idx = jnp.asarray(send)

    def mix(w: jax.Array, x: jax.Array) -> jax.Array:
        wd = w.astype(x.dtype)
        own = bcast(jnp.diagonal(wd), x.ndim) * x
        coeff = wd[recv_idx, send_idx]
        gathered = bcast(coeff, x.ndim) * x[send_idx]
        return own + jax.ops.segment_sum(
            gathered, recv_idx, num_segments=n, indices_are_sorted=True)

    return mix


if make_sparse_gossip.__doc__:  # stripped under python -OO
    make_sparse_gossip.__doc__ %= ELL_MAX_DEG


def lattice_max_degree(graphs) -> int:
    """The max degree over an R-run graph lattice — the shared ELL width
    (and the TPU edge-blocked-kernel eligibility bound)."""
    return max((int(g.degrees.max()) if g.n and g.num_edges else 0)
               for g in graphs)


def stacked_ell_tables(graphs):
    """Per-run ELL neighbour tables for a topology lattice, stacked.

    Every run's neighbour lists are padded to the lattice-wide max degree;
    padded slots point at the row's own index so a weight of 0 makes them
    exact +0.0 contributions.  Shared by the XLA stacked-ELL mix and the
    Pallas kernel wrappers so the paths can never drift.

    Returns:
      (nbr, valid, max_deg): nbr (R, n, max(max_deg, 1)) int32 and
      valid (same shape) bool marking real edges.
    """
    n = graphs[0].n
    max_deg = max(lattice_max_degree(graphs), 1)
    nbr = np.tile(np.arange(n, dtype=np.int32)[None, :, None],
                  (len(graphs), 1, max_deg))
    valid = np.zeros((len(graphs), n, max_deg), dtype=bool)
    for r, g in enumerate(graphs):
        adj = np.asarray(g.adjacency)
        for i in range(n):
            js = np.flatnonzero(adj[i])
            nbr[r, i, :len(js)] = js
            valid[r, i, :len(js)] = True
    return nbr, valid, max_deg


def make_sparse_gossip_batched(graphs):
    """Neighbour-only gossip over an R-run topology lattice (sweep engine).

    The stacked-ELL generalisation of :func:`make_sparse_gossip`: each run's
    neighbour list is padded to the lattice-wide max degree (padding points
    at the row's own agent with weight 0 — a +0.0 contribution, so every
    run's slice is bit-identical to its own single-run ELL mix), and the mix
    is max_deg fused gather-multiply-add passes over the whole (R, n, D)
    buffer.  Runs whose graph has no edges (FedAvg members of a mixed
    lattice, given W = I) reduce exactly to ``y = x``.  Lattices whose max
    degree exceeds the single-run CSR threshold still use the stacked ELL —
    the summation order then differs from the single-run CSR path (same
    math, 1e-5 equivalence instead of bit-exactness).

    Returns:
      mix(w, x) -> y for w (R, n, n), x (R, n, ...).
    """
    nbr, valid, max_deg = stacked_ell_tables(graphs)
    nbr_j = jnp.asarray(nbr)
    pad_j = jnp.asarray(~valid)

    def bcast(v, ndim):
        return v[(...,) + (None,) * (ndim - 2)]

    def mix(w: jax.Array, x: jax.Array) -> jax.Array:
        wd = w.astype(x.dtype)
        wv = jnp.where(pad_j, 0, jnp.take_along_axis(wd, nbr_j, axis=2))
        y = bcast(jnp.diagonal(wd, axis1=1, axis2=2), x.ndim) * x
        for k in range(max_deg):
            gathered = jnp.take_along_axis(
                x, nbr_j[:, :, k][(...,) + (None,) * (x.ndim - 2)], axis=1)
            y = y + bcast(wv[:, :, k], x.ndim) * gathered
        return y

    return mix


def make_sparse_gossip_tree(graph: topo.Graph):
    """Leaf-wise application of :func:`make_sparse_gossip` to stacked pytrees
    (the tree-engine ``gossip_impl='sparse'`` path)."""
    mix = make_sparse_gossip(graph)

    def gossip(w: jax.Array, stacked: object) -> object:
        return jax.tree.map(lambda leaf: mix(w, leaf), stacked)

    return gossip


def make_permute_gossip(graph: topo.Graph, mesh: jax.sharding.Mesh,
                        agent_axes: str | tuple[str, ...],
                        leaf_specs: object | None = None,
                        exchange_dtype=None):
    """Build a neighbour-only gossip function for a *static* topology.

    The graph's directed edges are decomposed into permutation rounds
    (:func:`repro.core.topology.permutation_schedule`); each round is one
    ``jax.lax.ppermute`` over the agent mesh axes — each agent sends/receives
    only its |deg| neighbours' parameters (O(deg·d) bytes) instead of the
    dense einsum's all-gather over every agent (O(n·d)).  Mixing *weights*
    may still be random per step (link failures): the sampled W is passed in
    and each device reads its own row.

    Requires n == prod(mesh.shape[a] for a in agent_axes): one agent per
    agent-axis slice.

    Args:
      leaf_specs: optional pytree of PartitionSpecs matching the stacked
        params (agent dim first, e.g. from sharding.param_pspecs) so the
        shard_map preserves inner tensor-parallel sharding.  Defaults to
        agents-only sharding.
      exchange_dtype: cast leaves to this dtype for the exchange and back
        (a simple bf16 wire cast; the full §Perf iteration A2 compression
        subsystem — int8/top-k payloads with error feedback — lives in
        repro.core.compress and the flat/sharded engines), accumulate in
        f32.

    Returns:
      gossip(w, stacked) -> stacked, usable under jit on the mesh.
    """
    if isinstance(agent_axes, str):
        agent_axes = (agent_axes,)
    n_mesh = int(np.prod([mesh.shape[a] for a in agent_axes]))
    if graph.n != n_mesh:
        raise ValueError(
            f"permute gossip needs one agent per mesh slice: graph has "
            f"{graph.n} agents but agent axes {agent_axes} have {n_mesh}")
    schedule = topo.permutation_schedule(graph)
    # ppermute takes (src, dst) pairs; round r: i receives from perm[i].
    perm_pairs = [
        tuple((int(p[i]), i) for i in range(graph.n) if p[i] != i)
        for p in schedule
    ]
    axis_name = agent_axes if len(agent_axes) > 1 else agent_axes[0]

    def per_shard(w: jax.Array, x: jax.Array) -> jax.Array:
        # x: (1, ...) — this device's agent block. w: (n, n) replicated.
        me = jax.lax.axis_index(axis_name)
        my_row = jax.lax.dynamic_slice_in_dim(w, me, 1, axis=0)[0]  # (n,)
        xs = x if exchange_dtype is None else x.astype(exchange_dtype)
        acc = x.astype(jnp.float32) * my_row[me]  # self weight W_ii
        for pairs, perm in zip(perm_pairs, schedule):
            recv = jax.lax.ppermute(xs, axis_name=axis_name, perm=pairs)
            src = jnp.asarray(perm, dtype=jnp.int32)[me]
            # Idle rounds (perm[me] == me) must not double-count self.
            coeff = jnp.where(src == me, 0.0, my_row[src])
            acc = acc + coeff * recv.astype(jnp.float32)
        return acc.astype(x.dtype)

    def _shard_map(fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    # One shard-mapped fn per distinct leaf spec, built once at factory time
    # (previously rebuilt per leaf on every gossip() call — pure retracing
    # overhead).  Specs are hashable, so unseen ones (leaf_specs=None with a
    # new leaf rank) memoise on first use.
    _mix_fns: dict = {}

    def _mix_for(spec: P):
        fn = _mix_fns.get(spec)
        if fn is None:
            fn = _shard_map(per_shard, in_specs=(P(None, None), spec),
                            out_specs=spec)
            _mix_fns[spec] = fn
        return fn

    if leaf_specs is not None:
        for s in jax.tree.leaves(leaf_specs,
                                 is_leaf=lambda x: isinstance(x, P)):
            _mix_for(s)

    def gossip(w: jax.Array, stacked: object) -> object:
        def mix(leaf: jax.Array, spec) -> jax.Array:
            if spec is None:
                spec = P(axis_name, *([None] * (leaf.ndim - 1)))
            return _mix_for(spec)(w, leaf)
        if leaf_specs is None:
            return jax.tree.map(lambda l: mix(l, None), stacked)
        return jax.tree.map(mix, stacked, leaf_specs,
                            is_leaf=lambda x: x is None)
    return gossip


def gossip_mix_permute(w: jax.Array, stacked: object, *,
                       graph: topo.Graph, mesh: jax.sharding.Mesh,
                       agent_axes: str | tuple[str, ...]) -> object:
    """One-shot convenience wrapper over :func:`make_permute_gossip`."""
    return make_permute_gossip(graph, mesh, agent_axes)(w, stacked)
