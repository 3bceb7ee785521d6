"""Device-sharded flat FedDec engine: the (n_agents, D) buffer over a mesh.

The flat engine (repro.core.flat) made Algorithm 1's hot loop a handful of
whole-buffer ops on one contiguous ``(n_agents, D)`` buffer — but on a single
device, so n_agents × D is capped by one device's HBM and FLOPs.  This module
shards the **agent axis** of that same buffer over a mesh axis with
``shard_map``: each device owns a contiguous block of ``n_agents // n_shards``
rows (agents-per-device ≥ 1 — the block-sharded layout), and every Algorithm-1
op becomes a per-shard op plus the minimal collective:

  * local SGD / optimizer update — embarrassingly parallel per shard: the
    same elementwise pass over the local ``(n_local, D)`` block, zero
    communication;
  * dense gossip ``x_i ← Σ_j W_ij x_j`` — each shard contracts its *column*
    block of W against its rows (``W[:, cols] @ x_blk``) and a single
    ``psum_scatter`` over the agent axis both sums the partials and hands
    every shard exactly its row block: no all-gather of X ever materialises;
  * sparse / ring gossip — a ``ppermute`` **halo exchange** over only the
    graph's *cut* edges: the base graph is collapsed to its block quotient
    (shards adjacent iff any edge crosses between their blocks), the quotient
    is decomposed into permutation rounds
    (:func:`repro.core.topology.permutation_schedule` — the same machinery as
    :func:`repro.core.gossip.make_permute_gossip`, generalized from the
    one-agent-per-device tree layout to the block-sharded flat layout), and
    each round is one ``ppermute`` of the local block followed by an
    ``(n_local, n_local) @ (n_local, D)`` sub-block contraction.  Intra-block
    edges cost no communication at all; ``gossip_impl='pallas'`` runs every
    sub-block contraction through the Pallas streaming kernel
    (kernels.ops.gossip_mix) per shard;
  * server round (lines 8–10) — each shard contracts its slice of the c/K
    participation weights against its block, one ``psum`` of the resulting
    ``(D,)`` vector forms z, and the broadcast back is a local
    ``broadcast_to``: the paper's "low-bandwidth, infrequent" server link is
    exactly one (D,)-sized all-reduce.

Correctness contract: a sharded round computes the same trajectory as the
single-device flat engine within 1e-5 (tests/test_sharded_engine.py) — the
per-step randomness is bit-identical (every shard derives the *full*
``split(key_grad, n_agents)`` key array replicated and slices its rows), and
each collective is the single-device contraction with the j-sum reordered
across devices.  Everything here is exercisable on CPU-only CI via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import compress as compress_lib
from repro.core import engine
from repro.core import server as server_lib
from repro.core import topology as topo
from repro.core.feddec import FedDecConfig
from repro.core.flat import FlatFedState, FlatSpec

__all__ = ["quotient_graph", "cut_edge_stats", "boundary_row_split",
           "make_sharded_gossip", "make_sharded_ef_gossip",
           "make_sharded_feddec_step", "make_sharded_feddec_round",
           "flat_state_specs", "shard_flat_state", "agent_axis_size"]

GradFn = Callable[[Any, Any, jax.Array], tuple[jax.Array, Any]]
LrFn = Callable[[jax.Array], jax.Array]


def _shard_map(fn, mesh, in_specs, out_specs, manual=None):
    """``jax.shard_map`` manual over the ``manual`` mesh axes (all by
    default).

    The 2-D engine runs manual over 'agents' only, leaving 'model' to the
    GSPMD partitioner, so each agent replica's compute is tensor-sharded by
    the compiler while the gossip / server collectives stay hand-written
    over the agent axis.  A region nested inside that one passes the
    context's abstract mesh (``jax.sharding.get_abstract_mesh()``, whose
    agent axis is already manual) and ``manual={'model'}``."""
    kw = {} if manual is None else {"axis_names": frozenset(manual)}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kw)


def agent_axis_size(mesh: jax.sharding.Mesh,
                    axis_name: str | tuple[str, ...]) -> int:
    """Number of shards the agent dim is split into on this mesh."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    return int(np.prod([mesh.shape[a] for a in axes]))


# ---------------------------------------------------------------------------
# Block-quotient topology: which shards must talk at all
# ---------------------------------------------------------------------------


def quotient_graph(graph: topo.Graph, n_shards: int) -> topo.Graph:
    """Collapse the agent graph to its shard-block quotient.

    Agents are block-sharded contiguously (shard s owns rows
    ``[s·n_local, (s+1)·n_local)``); shards r ≠ s are adjacent iff **any**
    base edge crosses between their blocks.  This is the communication
    pattern of the halo exchange: intra-block edges never leave the device,
    and the ``ppermute`` schedule only covers the quotient's edges.
    """
    n = graph.n
    if n_shards < 1 or n % n_shards:
        raise ValueError(f"n_shards must divide n_agents: {n_shards} ∤ {n}")
    n_local = n // n_shards
    adj = np.asarray(graph.adjacency)
    blocks = adj.reshape(n_shards, n_local, n_shards, n_local).any(axis=(1, 3))
    np.fill_diagonal(blocks, False)
    return topo.Graph(blocks, name=f"quotient({graph.name}/{n_shards})")


def cut_edge_stats(graph: topo.Graph, n_shards: int) -> dict:
    """Static communication metadata of the sharded layout.

    ``num_cut_edges`` counts *directed* base-graph edges whose endpoints live
    on different shards — the edges the halo exchange exists to serve;
    ``num_halo_rounds`` is the length of the quotient's permutation schedule
    (each round moves one (n_local, D) block per participating shard).  The
    dense path's psum_scatter is oblivious to the graph, so the ratio of the
    two byte models is the sharding win of the sparse path — see
    :func:`repro.launch.analysis.sharded_gossip_cost_model`.
    """
    n = graph.n
    n_local = n // n_shards
    recv, send = np.nonzero(np.asarray(graph.adjacency))
    cut = (recv // n_local) != (send // n_local)
    q = quotient_graph(graph, n_shards)
    schedule = topo.permutation_schedule(q)
    return {
        "n_agents": n,
        "n_shards": n_shards,
        "agents_per_shard": n_local,
        "num_directed_edges": int(len(recv)),
        "num_cut_edges": int(cut.sum()),
        "num_halo_rounds": len(schedule),
        "quotient_max_degree": int(q.degrees.max()) if q.n else 0,
    }


def boundary_row_split(graph: topo.Graph, n_shards: int) -> dict:
    """Split each shard's rows into boundary (on a cut edge) vs interior.

    A local row is *boundary* iff it has any base-graph edge (in either
    direction) to an agent on another shard — only those rows' values can
    appear in a neighbouring shard's mix, and only those rows can consume a
    received value.  The halo therefore only needs to move each shard's
    boundary slice, and everything a shard computes from purely local data
    (its interior rows, plus every row's own-block contribution) is
    independent of the in-flight exchange — the overlap window
    ``analysis.roundfuse_cost_model`` predicts.

    Returns static (host-side) tables, padded to the lattice-wide max
    boundary count ``b_max`` so the per-round ``ppermute`` payload has one
    shape for every shard:

      ``index``    (n_shards, b_max) int32 — local row ids of shard s's
                   boundary rows (padded with 0);
      ``valid``    (n_shards, b_max) bool — False on padding;
      ``counts``   (n_shards,) int — true boundary rows per shard;
      plus scalars ``n_local``, ``b_max``, ``interior_min`` (the smallest
      per-shard interior count — the guaranteed overlap compute).
    """
    n = graph.n
    if n_shards < 1 or n % n_shards:
        raise ValueError(f"n_shards must divide n_agents: {n_shards} ∤ {n}")
    n_local = n // n_shards
    adj = np.asarray(graph.adjacency)
    sym = adj | adj.T
    shard_of = np.arange(n) // n_local
    cross = sym & (shard_of[:, None] != shard_of[None, :])
    per = cross.any(axis=1).reshape(n_shards, n_local)
    counts = per.sum(axis=1)
    b_max = int(counts.max()) if n_shards > 0 else 0
    index = np.zeros((n_shards, b_max), np.int32)
    valid = np.zeros((n_shards, b_max), bool)
    for s in range(n_shards):
        rows = np.nonzero(per[s])[0]
        index[s, :len(rows)] = rows
        valid[s, :len(rows)] = True
    return {"index": index, "valid": valid,
            "counts": counts.astype(np.int64),
            "n_local": n_local, "b_max": b_max,
            "interior_min": int(n_local - counts.max()) if n_shards else 0}


# ---------------------------------------------------------------------------
# Per-shard gossip mixers
# ---------------------------------------------------------------------------


def _halo_setup(cfg: FedDecConfig, n_shards: int):
    """Static ppermute metadata of the quotient graph, shared by the
    uncompressed and compressed halo mixers: ``perms`` is (R, S) int32
    (round r, shard d receives shard perms[r, d]'s block), ``pairs`` the
    per-round (src, dst) ppermute arguments, and ``split`` the boundary /
    interior row tables (:func:`boundary_row_split`) that size the halo
    payload."""
    q = quotient_graph(cfg.mixing.graph, n_shards)
    schedule = topo.permutation_schedule(q)
    perms = jnp.asarray(
        np.stack(schedule) if schedule
        else np.zeros((0, n_shards), np.int64), jnp.int32)
    pairs = [tuple((int(p[d]), d) for d in range(n_shards) if p[d] != d)
             for p in schedule]
    split = boundary_row_split(cfg.mixing.graph, n_shards)
    return perms, pairs, split


def _boundary_wcols(w_rows, b_index, b_valid, src, me, n_local):
    """Round-r cut-edge weight columns W[my rows, src's boundary rows] as an
    (n_local, b_max) slab: padding columns are masked off and idle shards
    this round (perm[me] == me) received zeros and must not re-add their
    own block."""
    cols = src * n_local + jnp.take(b_index, src, axis=0)
    wc = jnp.take(w_rows, cols, axis=1)
    keep = jnp.take(b_valid, src, axis=0) & (src != me)
    return wc * keep.astype(wc.dtype)[None, :]


def _blk_mix_for(impl: str, block_d: int | None):
    """The (n_local, n_local) @ (n_local, D) sub-block contraction: the
    Pallas streaming kernel for impl='pallas', the XLA einsum otherwise."""
    if impl == "pallas":
        from repro.kernels import ops as kernel_ops

        def blk_mix(wb, xb):
            if block_d is None:
                return kernel_ops.gossip_mix(wb, xb)
            return kernel_ops.gossip_mix(wb, xb, block_d=block_d)
        return blk_mix

    def blk_mix(wb, xb):
        return jnp.einsum("ij,jd->id", wb.astype(xb.dtype), xb,
                          precision=jax.lax.Precision.HIGHEST)
    return blk_mix


def _make_shard_mixer(cfg: FedDecConfig, axis_name, n_shards: int,
                      block_d: int | None = None, model_ax=None):
    """gossip_impl → per-shard mix(w, x_blk, me) -> y_blk.

    ``w`` is the full replicated (n, n) mixing matrix (weights stay random
    per step — link failures zero entries; the *support* metadata below is
    static), ``x_blk`` the shard's (n_local, D) row block, ``me`` the shard
    index on the agent axis.

    ``model_ax`` (the model mesh axis) is set by the 2-D lowering: the
    caller's region is manual over the agent axis with the model axis left
    to GSPMD, and gossip commutes with that column sharding (W contracts
    the agent index, elementwise in D — ALGORITHM.md) so the dense
    psum_scatter path needs no change at all.  The ppermute halo cannot run
    under a partially-auto region (the partitioner rejects it), so the halo
    paths wrap themselves in an inner fully-manual shard_map over the model
    axis and exchange (n_local, D/M) sub-blocks — the halo bytes shrink by
    M along with the state.
    """
    impl = cfg.gossip_impl
    n = cfg.n_agents
    n_local = n // n_shards

    if impl == "none":
        return lambda w, x_blk, me: x_blk

    if impl == "dense":
        def mix(w, x_blk, me):
            cols = jax.lax.dynamic_slice_in_dim(w, me * n_local, n_local,
                                                axis=1)
            partial = jnp.einsum("ij,jd->id", cols.astype(x_blk.dtype),
                                 x_blk, precision=jax.lax.Precision.HIGHEST)
            if n_shards == 1:
                return partial
            return jax.lax.psum_scatter(partial, axis_name,
                                        scatter_dimension=0, tiled=True)
        return mix

    if impl in ("sparse", "pallas"):
        perms, pairs, split = _halo_setup(cfg, n_shards)
        blk_mix = _blk_mix_for(impl, block_d)
        b_index = jnp.asarray(split["index"])
        b_valid = jnp.asarray(split["valid"])

        def halo(w, x_blk, me):
            # boundary/interior overlap: every halo round's (b_max, D)
            # boundary payload is gathered and its ppermute issued *before*
            # any local compute — the own-block contraction (interior rows
            # plus every row's intra-block terms) then runs while the cut
            # edges are in flight, and only the final per-round cut-edge
            # slabs W[my rows, src boundary] @ recv wait on arrival
            lo = me * n_local
            payload = jnp.take(x_blk, jnp.take(b_index, me, axis=0), axis=0)
            recvs = [jax.lax.ppermute(payload, axis_name, perm=pr)
                     for pr in pairs]
            w_rows = jax.lax.dynamic_slice_in_dim(w, lo, n_local, axis=0)
            own = jax.lax.dynamic_slice_in_dim(w_rows, lo, n_local, axis=1)
            y = blk_mix(own, x_blk)
            for r, recv in enumerate(recvs):
                wc = _boundary_wcols(w_rows, b_index, b_valid, perms[r, me],
                                     me, n_local)
                y = y + jnp.einsum("ib,bd->id", wc.astype(x_blk.dtype),
                                   recv,
                                   precision=jax.lax.Precision.HIGHEST)
            return y

        if model_ax is None:
            return halo

        def mix(w, x_blk, me):
            inner = _shard_map(halo, jax.sharding.get_abstract_mesh(),
                               in_specs=(P(None, None), P(None, model_ax),
                                         P()),
                               out_specs=P(None, model_ax),
                               manual={model_ax})
            return inner(w, x_blk, me)
        return mix

    raise engine.unknown_gossip_impl(impl)


def _make_compressed_shard_mixer(cfg: FedDecConfig, axis_name, n_shards: int,
                                 compressor, block_d: int | None = None,
                                 model_ax=None):
    """Compressed-gossip per-shard mixer (repro.core.compress semantics):

        mix(w, p_blk, s_blk, payload, me) -> y_blk
        y_i = W_ii p_i + Σ_{j≠i} W_ij s_j

    ``p_blk`` is the shard's full-precision (n_local, D) block, ``s_blk``
    its dequantized compressed values, ``payload`` the encoded wire form.
    The dense path contracts against s and psum_scatters f32 partials (the
    collective is graph-oblivious — compression there only changes the
    *semantics*); the sparse/pallas halo ``ppermute``s the **encoded
    payload** itself (int8 buffer + scales / top-k values + indices), so
    the cut-edge collective bytes in the compiled HLO shrink by the
    compressor's payload ratio, and each receiver fuses decode into its
    sub-block contraction.
    """
    impl = cfg.gossip_impl
    n = cfg.n_agents
    n_local = n // n_shards

    def diag_blk(w, me):
        return jax.lax.dynamic_slice_in_dim(
            jnp.diagonal(w), me * n_local, n_local)

    if impl == "dense":
        def mix(w, p_blk, s_blk, payload, me):
            cols = jax.lax.dynamic_slice_in_dim(w, me * n_local, n_local,
                                                axis=1)
            partial = jnp.einsum("ij,jd->id", cols.astype(s_blk.dtype),
                                 s_blk, precision=jax.lax.Precision.HIGHEST)
            y = partial if n_shards == 1 else jax.lax.psum_scatter(
                partial, axis_name, scatter_dimension=0, tiled=True)
            dg = diag_blk(w, me).astype(p_blk.dtype)[:, None]
            return y + dg * (p_blk - s_blk)
        return mix

    if impl in ("sparse", "pallas"):
        perms, pairs, split = _halo_setup(cfg, n_shards)
        blk_mix = _blk_mix_for(impl, block_d)
        b_index = jnp.asarray(split["index"])
        b_valid = jnp.asarray(split["valid"])

        def halo(w, p_blk, s_blk, payload, me):
            # the halo moves the *encoded* payload, leaf by leaf, and only
            # its boundary rows; all ppermutes are issued before the local
            # own-block mix so the cut-edge exchange overlaps it (the codec
            # is per-row, so decoding a row slice equals slicing the decode)
            lo = me * n_local
            idx_me = jnp.take(b_index, me, axis=0)
            bpay = jax.tree.map(lambda a: jnp.take(a, idx_me, axis=0),
                                payload)
            recvs = [jax.tree.map(
                lambda a: jax.lax.ppermute(a, axis_name, perm=pr), bpay)
                for pr in pairs]
            w_rows = jax.lax.dynamic_slice_in_dim(w, lo, n_local, axis=0)
            own = jax.lax.dynamic_slice_in_dim(w_rows, lo, n_local, axis=1)
            dg = diag_blk(w, me).astype(p_blk.dtype)[:, None]
            y = blk_mix(own, s_blk) + dg * (p_blk - s_blk)
            for r, recv in enumerate(recvs):
                s_recv = compressor.decode(recv, p_blk.dtype,
                                           p_blk.shape[1])
                wc = _boundary_wcols(w_rows, b_index, b_valid, perms[r, me],
                                     me, n_local)
                y = y + jnp.einsum("ib,bd->id", wc.astype(p_blk.dtype),
                                   s_recv,
                                   precision=jax.lax.Precision.HIGHEST)
            return y

        if model_ax is None:
            return halo

        def mix(w, p_blk, s_blk, payload, me):
            # encode ran under GSPMD (per-row scales see the full D axis —
            # identical numerics to the flat engine); only the halo drops
            # to the manual 2-D region.  D-sized payload leaves travel as
            # D/M sub-blocks; per-row scalars (scales) replicate over
            # 'model' — elementwise decode is exact on the slice.
            pay_specs = jax.tree.map(
                lambda a: P(None, model_ax) if a.ndim == 2 else P(None),
                payload)
            inner = _shard_map(
                halo, jax.sharding.get_abstract_mesh(),
                in_specs=(P(None, None), P(None, model_ax),
                          P(None, model_ax), pay_specs, P()),
                out_specs=P(None, model_ax), manual={model_ax})
            return inner(w, p_blk, s_blk, payload, me)
        return mix

    raise engine.unknown_gossip_impl(impl)


def make_sharded_gossip(cfg: FedDecConfig, mesh: jax.sharding.Mesh,
                        axis_name: str | tuple[str, ...] = "agents",
                        block_d: int | None = None):
    """Whole-buffer gossip on an agent-sharded (n, D) buffer.

    The block-sharded generalization of
    :func:`repro.core.gossip.make_permute_gossip`: any
    agents-per-device ≥ 1, flat single-buffer layout, and the three flat
    impls (dense psum_scatter contraction / sparse ppermute halo / per-shard
    Pallas kernel) instead of the per-leaf schedule.

    Returns ``gossip(w, x) -> y`` for ``x`` of shape (n_agents, D) sharded
    ``P(axis_name, None)``; usable under jit on the mesh.
    """
    n_shards = agent_axis_size(mesh, axis_name)
    if cfg.n_agents % n_shards:
        raise ValueError(
            f"agent axis {axis_name!r} has {n_shards} shards which must "
            f"divide n_agents={cfg.n_agents}")
    ax = axis_name if isinstance(axis_name, str) or len(axis_name) > 1 \
        else axis_name[0]
    mixer = _make_shard_mixer(cfg, ax, n_shards, block_d=block_d)

    def per_shard(w, x_blk):
        return mixer(w, x_blk, jax.lax.axis_index(ax))

    return _shard_map(per_shard, mesh, in_specs=(P(None, None), P(ax)),
                      out_specs=P(ax))


def make_sharded_ef_gossip(cfg: FedDecConfig, mesh: jax.sharding.Mesh,
                           axis_name: str | tuple[str, ...] = "agents",
                           block_d: int | None = None):
    """Compressed whole-buffer gossip with error feedback on the mesh.

    The standalone counterpart of :func:`repro.core.compress
    .make_flat_ef_gossip` for an agent-sharded (n, D) buffer — the op the
    compressed step body executes, exposed for benchmarks/tests:

        gossip(w, p, res, key_c) -> (y, new_res)

    with ``p``/``res`` sharded ``P(axis_name)`` and ``key_c`` the step's
    codec key (per-agent keys are derived replicated and row-sliced, so the
    result matches the single-device EF gossip on the same inputs).  The
    sparse/pallas impls ppermute the *encoded* halo payload.  With
    ``cfg.gossip_compress='none'`` this degrades to
    :func:`make_sharded_gossip` plus an untouched ().
    """
    compressor = compress_lib.parse_compress(cfg.gossip_compress)
    if compressor is None or cfg.gossip_impl == "none":
        # same bypass as the engines: W = I exchanges nothing to compress
        plain = make_sharded_gossip(cfg, mesh, axis_name, block_d=block_d)
        return lambda w, p, res, key_c: (plain(w, p), res)
    n_shards = agent_axis_size(mesh, axis_name)
    if cfg.n_agents % n_shards:
        raise ValueError(
            f"agent axis {axis_name!r} has {n_shards} shards which must "
            f"divide n_agents={cfg.n_agents}")
    ax = axis_name if isinstance(axis_name, str) or len(axis_name) > 1 \
        else axis_name[0]
    cmixer = _make_compressed_shard_mixer(cfg, ax, n_shards,
                                          compressor, block_d=block_d)
    n_agents = cfg.n_agents
    n_local = n_agents // n_shards

    def per_shard(w, p_blk, res_blk, key_c):
        me = jax.lax.axis_index(ax)
        payload, s_blk, new_res = _encode_shard_block(
            compressor, key_c, n_agents, n_local, me, p_blk, res_blk)
        return cmixer(w, p_blk, s_blk, payload, me), new_res

    return _shard_map(per_shard, mesh,
                      in_specs=(P(None, None), P(ax), P(ax), P()),
                      out_specs=(P(ax), P(ax)))


# ---------------------------------------------------------------------------
# State placement helpers
# ---------------------------------------------------------------------------


def _leaf_spec(leaf, axis_name, model_axis=None) -> P:
    """THE sharding rule for flat-engine state leaves (single source of
    truth for executors' shard_map specs and shard_flat_state placement):
    (n, D) buffers follow the agent sharding — and with ``model_axis`` set,
    the 2-D ``P(agents, model)`` column sharding — scalars (step, adamw
    count) replicate.  ``leaf`` may be a live array or a ShapeDtypeStruct."""
    if getattr(leaf, "ndim", 0) != 2:
        return P()
    if model_axis is None:
        return P(axis_name)
    return P(axis_name, model_axis)


def _opt_specs(optimizer, spec: FlatSpec, n_agents: int, axis_name,
               model_axis=None) -> Any:
    """PartitionSpecs for the flat optimizer buffers."""
    if optimizer is None:
        return ()
    struct = jax.eval_shape(
        optimizer.init, jax.ShapeDtypeStruct((n_agents, spec.d), spec.dtype))
    return jax.tree.map(lambda s: _leaf_spec(s, axis_name, model_axis),
                        struct)


def flat_state_specs(optimizer, spec: FlatSpec, n_agents: int,
                     axis_name: str | tuple[str, ...] = "agents",
                     compress: str = "none",
                     model_axis: str | None = None) -> FlatFedState:
    """FlatFedState pytree of PartitionSpecs for the sharded engine.

    With ``model_axis`` set, every (n, D) leaf is column-sharded over it
    too — the 2-D placement whose per-device bytes are ``n/A · D/M · 4``.
    """
    buf = _leaf_spec(jax.ShapeDtypeStruct((n_agents, spec.d), spec.dtype),
                     axis_name, model_axis)
    return FlatFedState(
        flat=buf, step=P(),
        opt_state=_opt_specs(optimizer, spec, n_agents, axis_name,
                             model_axis),
        residual=() if compress == "none" else buf)


def shard_flat_state(state: FlatFedState, mesh: jax.sharding.Mesh,
                     axis_name: str | tuple[str, ...] = "agents",
                     model_axis: str | None = None) -> FlatFedState:
    """Place a FlatFedState on the mesh with the agent dim block-sharded
    (and, with ``model_axis``, the D dim column-sharded)."""
    specs = FlatFedState(
        flat=_leaf_spec(state.flat, axis_name, model_axis), step=P(),
        opt_state=jax.tree.map(
            lambda l: _leaf_spec(l, axis_name, model_axis),
            state.opt_state),
        residual=jax.tree.map(
            lambda l: _leaf_spec(l, axis_name, model_axis),
            state.residual))
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(state, shardings)


# ---------------------------------------------------------------------------
# The sharded engine
# ---------------------------------------------------------------------------


def _slice_agent_keys(keys: jax.Array, lo: jax.Array, n_local: int):
    """Rows [lo, lo+n_local) of a typed key array (exactly the keys the
    single-device engine's split(key_grad, n) would hand these agents)."""
    data = jax.random.key_data(keys)
    blk = jax.lax.dynamic_slice_in_dim(data, lo, n_local, axis=0)
    return jax.random.wrap_key_data(blk)


def _encode_shard_block(compressor, key_c, n_agents: int, n_local: int,
                        me, x_blk, res_blk):
    """Per-shard EF encode → (payload, s_blk, new_res).

    The per-agent codec keys are derived replicated and row-sliced (like
    the grad keys), so agent i's rounding noise — and with it s_i and the
    residual — matches the single-device flat engine bit for bit.
    """
    keys = _slice_agent_keys(
        jax.random.split(key_c, n_agents), me * n_local, n_local) \
        if compressor.needs_key else None
    u = x_blk + res_blk
    payload = compressor.encode(keys, u)
    s_blk = compressor.decode(payload, u.dtype, u.shape[1])
    return payload, s_blk, u - s_blk


def _shard_ops(cfg: FedDecConfig, spec: FlatSpec, grad_fn: GradFn,
               lr_fn: LrFn, axis_name, n_shards: int, optimizer,
               block_d: int | None, me_fn=None,
               model_ax=None) -> engine.EngineOps:
    """The sharded engine's vtable for the shared Algorithm-1 body.

    The carry is the per-shard tuple ``(x_blk, res_blk, opt_blk, t)``;
    replicated scalars stay bit-identical to repro.core.flat's step so
    trajectories match.

    ``me_fn`` supplies the shard index on the agent axis; the default is
    ``lax.axis_index``, but the 2-D lowering's partially-auto region cannot
    lower that (the partitioner has no device id under GSPMD) and injects
    the index from a sharded iota input instead.  ``model_ax`` is
    forwarded to the gossip mixers (see :func:`_make_shard_mixer`).
    """
    n_agents = cfg.n_agents
    n_local = n_agents // n_shards
    if me_fn is None:
        def me_fn():
            return jax.lax.axis_index(axis_name)
    compressor = compress_lib.parse_compress(cfg.gossip_compress) \
        if cfg.gossip_impl != "none" else None
    if compressor is None:
        mixer = _make_shard_mixer(cfg, axis_name, n_shards, block_d=block_d,
                                  model_ax=model_ax)
    else:
        cmixer = _make_compressed_shard_mixer(cfg, axis_name, n_shards,
                                              compressor, block_d=block_d,
                                              model_ax=model_ax)

    def shard_server_round(key, x_blk, me):
        # lines 8–10 as psum + broadcast: every shard draws the same S_t
        # from the replicated key, contracts its weight slice, and the
        # (D,)-sized all-reduce is the entire server link
        counts = server_lib.sample_participants(key, n_agents, cfg.k)
        wts = server_lib.participant_weights(counts, cfg.k)
        w_blk = jax.lax.dynamic_slice_in_dim(wts, me * n_local, n_local)
        z = jnp.tensordot(w_blk.astype(x_blk.dtype), x_blk, axes=(0, 0))
        if n_shards > 1:
            z = jax.lax.psum(z, axis_name)
        return jnp.broadcast_to(z[None], x_blk.shape)

    def local_update(state, batch_blk, key_grad, eta):
        # lines 4–5: this shard's agents only; the full per-agent key array
        # is derived replicated and row-sliced so agent i's key matches the
        # single-device engine exactly
        x_blk, _, opt_blk, _ = state
        me = me_fn()
        params = spec.unflatten(x_blk)
        agent_keys = _slice_agent_keys(
            jax.random.split(key_grad, n_agents), me * n_local, n_local)
        losses, grads = jax.vmap(grad_fn)(params, batch_blk, agent_keys)
        g_blk = spec.flatten(grads)
        if optimizer is None:
            return losses, x_blk - eta.astype(spec.dtype) * g_blk, opt_blk
        x_half, new_opt = optimizer.update(x_blk, g_blk, opt_blk, eta)
        return losses, x_half, new_opt

    def gossip(w, x_half):
        return mixer(w, x_half, me_fn())

    def ef_gossip(w, x_half, res_blk, key_c):
        # the halo moves the encoded payload
        me = me_fn()
        payload, s_blk, new_res = _encode_shard_block(
            compressor, key_c, n_agents, n_local, me, x_half, res_blk)
        return cmixer(w, x_half, s_blk, payload, me), new_res

    def server(key_server, x_next, t):
        if not cfg.server_enabled:
            return x_next
        me = me_fn()
        return jax.lax.cond(
            (t + 1) % cfg.h == 0,
            lambda x: shard_server_round(key_server, x, me),
            lambda x: x,
            x_next)

    def finish(state, z_next, new_opt, new_res, t, losses, eta):
        loss = jnp.sum(losses)
        if n_shards > 1:
            loss = jax.lax.psum(loss, axis_name)
        metrics = {"loss": loss / n_agents, "eta": eta}
        return (z_next, new_res, new_opt, t + 1), metrics

    return engine.EngineOps(
        get_step=lambda s: s[3],
        derive_keys=lambda key, t: jax.random.split(
            jax.random.fold_in(key, t), 3),
        eta_fn=lr_fn,
        sample_w=cfg.mixing.sample,
        local_update=local_update,
        gossip=(lambda w, x: x) if compressor is not None else gossip,
        get_residual=lambda s: s[1],
        server=server,
        finish=finish,
        fold_codec=None if compressor is None else (
            lambda key_w: jax.random.fold_in(key_w, 1)),
        ef_gossip=None if compressor is None else ef_gossip)


def _build_per_shard_step(cfg: FedDecConfig, spec: FlatSpec, grad_fn: GradFn,
                          lr_fn: LrFn, axis_name, n_shards: int,
                          optimizer, block_d: int | None, me_fn=None,
                          model_ax=None):
    """step(x_blk, res_blk, opt_blk, t, batch_blk, key) over the shared
    body (t advances in the carry; callers thread it)."""
    body = engine.build_step_body(
        _shard_ops(cfg, spec, grad_fn, lr_fn, axis_name, n_shards,
                   optimizer, block_d, me_fn=me_fn, model_ax=model_ax))

    def step(x_blk, res_blk, opt_blk, t, batch_blk, key):
        (z, new_res, new_opt, _), metrics = body(
            (x_blk, res_blk, opt_blk, t), batch_blk, key)
        return z, new_res, new_opt, metrics

    return step


def _resolve_axis(mesh, axis_name):
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    for a in axes:
        if a not in mesh.shape:
            raise ValueError(f"mesh has no axis {a!r}: {mesh.shape}")
    return axes if len(axes) > 1 else axes[0]


def _validate(cfg, mesh, axis_name):
    n_shards = agent_axis_size(mesh, axis_name)
    if cfg.n_agents % n_shards:
        raise ValueError(
            f"n_agents={cfg.n_agents} must be divisible by the agent axis "
            f"size {n_shards} (block-sharded rows)")
    return n_shards


# ---------------------------------------------------------------------------
# The 2-D ('agents', 'model') lowering
# ---------------------------------------------------------------------------


def _validate_model_axis(cfg, spec, mesh, model_axis):
    if model_axis not in mesh.shape:
        raise ValueError(
            f"mesh has no model axis {model_axis!r}: {dict(mesh.shape)} "
            f"(build one with launch.mesh.make_fed_mesh)")
    m = mesh.shape[model_axis]
    if spec.d % m:
        raise ValueError(
            f"flat dim D={spec.d} must be divisible by the model axis "
            f"size {m} (column-sharded D/M sub-blocks)")
    if m > 1 and cfg.gossip_impl != "none" \
            and cfg.gossip_compress.startswith("topk"):
        raise engine.model_axis_conflict(
            "topk gossip compression (the payload indices address the "
            "full D axis)")
    return m


def _pin2d(mesh, ax, model_ax, tree):
    """Constrain every (n, D)-shaped leaf to the 2-D P(agents, model)
    placement — GSPMD would otherwise be free to keep the model dim
    replicated, which is exactly the memory blow-up this engine removes."""
    return jax.tree.map(
        lambda l: jax.lax.with_sharding_constraint(
            l, NamedSharding(mesh, P(ax, model_ax)))
        if getattr(l, "ndim", 0) == 2 else l, tree)


def _smap_step_2d(cfg, spec, grad_fn, lr_fn, mesh, ax, n_shards, model_ax,
                  optimizer, block_d):
    """The per-step executor of the 2-D engine: one shard_map, manual over
    the agent axis, with ``model_ax`` left to GSPMD.

    Inside the region every array keeps its logical per-shard shape
    ((n_local, D) blocks) while GSPMD tensor-shards the D dim over
    ``model_ax`` — so the gossip / server collectives stay the hand-written
    agent-axis ops of the 1-D engine and the per-replica model compute
    (grad, optimizer, mixing contractions) partitions over 'model' without
    any engine code knowing about it.  Two jaxlib constraints shape the
    region: ``lax.axis_index`` cannot lower under GSPMD, so the shard index
    rides in as a sharded iota input (``ids``, one int per agent shard, the
    local slice is ``ids[0]``); and ``ppermute`` cannot either, so the halo
    mixers drop into an inner fully-manual shard_map over 'model'
    (:func:`_make_shard_mixer`).
    """
    me_cell = []
    per_shard_body = _build_per_shard_step(
        cfg, spec, grad_fn, lr_fn, ax, n_shards, optimizer, block_d,
        me_fn=lambda: me_cell[-1], model_ax=model_ax)

    def per_shard(ids, x_blk, res_blk, opt_blk, t, batch_blk, key_data):
        # the PRNG key crosses the partially-auto boundary as raw u32 data:
        # the partitioner cannot tile-assign the extended key dtype there
        me_cell.append(ids[0])
        try:
            return per_shard_body(x_blk, res_blk, opt_blk, t, batch_blk,
                                  jax.random.wrap_key_data(key_data))
        finally:
            me_cell.pop()

    opt_specs = _opt_specs(optimizer, spec, cfg.n_agents, ax)
    res_specs = () if cfg.gossip_compress == "none" \
        or cfg.gossip_impl == "none" else P(ax)
    metric_specs = {"loss": P(), "eta": P()}
    smapped = _shard_map(
        per_shard, mesh,
        in_specs=(P(ax), P(ax), res_specs, opt_specs, P(), P(ax), P()),
        out_specs=(P(ax), res_specs, opt_specs, metric_specs),
        manual={ax})

    def call(state: FlatFedState, batch, key):
        ids = jax.lax.with_sharding_constraint(
            jnp.arange(n_shards, dtype=jnp.int32),
            NamedSharding(mesh, P(ax)))
        flat, res, opt, metrics = smapped(ids, state.flat, state.residual,
                                          state.opt_state, state.step,
                                          batch, jax.random.key_data(key))
        flat = _pin2d(mesh, ax, model_ax, flat)
        res = _pin2d(mesh, ax, model_ax, res)
        opt = _pin2d(mesh, ax, model_ax, opt)
        return flat, res, opt, metrics

    return call


def _lower_sharded_step_2d(cfg, spec, grad_fn, lr_fn, mesh, ax, n_shards,
                           model_ax, optimizer, block_d, donate, jit):
    call = _smap_step_2d(cfg, spec, grad_fn, lr_fn, mesh, ax, n_shards,
                         model_ax, optimizer, block_d)

    def step(state: FlatFedState, batch: Any, key: jax.Array):
        flat, res, opt, metrics = call(state, batch, key)
        return FlatFedState(flat=flat, step=state.step + 1,
                            opt_state=opt, residual=res), metrics

    return engine.finalize_executor(step, donate=donate, jit=jit)


def _lower_sharded_round_2d(cfg, spec, grad_fn, lr_fn, mesh, ax, n_shards,
                            model_ax, optimizer, block_d, donate, jit,
                            unroll):
    # The fused round inverts the 1-D nesting: lax.scan over the
    # shard_mapped step at the jit level, not a scan inside shard_map —
    # a scan whose ys cross a partially-auto region is rejected by the
    # partitioner.  Per-step metrics leave the region replicated and the
    # outer scan stacks them to (H,), matching the 1-D round's contract.
    call = _smap_step_2d(cfg, spec, grad_fn, lr_fn, mesh, ax, n_shards,
                         model_ax, optimizer, block_d)

    def round_fn(state: FlatFedState, batches: Any, key: jax.Array):
        def body(carry, batch):
            st = FlatFedState(flat=carry[0], step=carry[3],
                              opt_state=carry[2], residual=carry[1])
            flat, res, opt, metrics = call(st, batch, key)
            return (flat, res, opt, carry[3] + 1), metrics

        (flat, res, opt, t), metrics = jax.lax.scan(
            body, (state.flat, state.residual, state.opt_state, state.step),
            batches, unroll=unroll)
        return FlatFedState(flat=flat, step=t, opt_state=opt,
                            residual=res), metrics

    return engine.finalize_executor(round_fn, donate=donate, jit=jit)


def _lower_sharded_step(cfg: FedDecConfig, spec: FlatSpec,
                        grad_fn: GradFn, lr_fn: LrFn,
                        mesh: jax.sharding.Mesh, *,
                        axis_name: str | tuple[str, ...] = "agents",
                        optimizer=None, block_d: int | None = None,
                        donate: bool = True, jit: bool = True,
                        model_axis: str | None = None):
    ax = _resolve_axis(mesh, axis_name)
    n_shards = _validate(cfg, mesh, ax)
    if model_axis is not None:
        m = _validate_model_axis(cfg, spec, mesh, model_axis)
        if m > 1:
            return _lower_sharded_step_2d(
                cfg, spec, grad_fn, lr_fn, mesh, ax, n_shards, model_axis,
                optimizer, block_d, donate, jit)
    per_shard = _build_per_shard_step(cfg, spec, grad_fn, lr_fn, ax,
                                      n_shards, optimizer, block_d)
    opt_specs = _opt_specs(optimizer, spec, cfg.n_agents, ax)
    res_specs = () if cfg.gossip_compress == "none" \
        or cfg.gossip_impl == "none" else P(ax)
    metric_specs = {"loss": P(), "eta": P()}
    smapped = _shard_map(
        per_shard, mesh,
        in_specs=(P(ax), res_specs, opt_specs, P(), P(ax), P()),
        out_specs=(P(ax), res_specs, opt_specs, metric_specs))

    def step(state: FlatFedState, batch: Any, key: jax.Array):
        flat, res, opt, metrics = smapped(state.flat, state.residual,
                                          state.opt_state, state.step,
                                          batch, key)
        return FlatFedState(flat=flat, step=state.step + 1,
                            opt_state=opt, residual=res), metrics

    return engine.finalize_executor(step, donate=donate, jit=jit)


def make_sharded_feddec_step(cfg: FedDecConfig, spec: FlatSpec,
                             grad_fn: GradFn, lr_fn: LrFn,
                             mesh: jax.sharding.Mesh, *,
                             axis_name: str | tuple[str, ...] = "agents",
                             optimizer=None, block_d: int | None = None,
                             donate: bool = True, jit: bool = True,
                             model_axis: str | None = None):
    """One-iteration sharded executor: step(state, batch, key) carrying a
    FlatFedState whose buffer rows are block-sharded over ``axis_name``.

    Same contract as repro.core.flat.make_flat_feddec_step; batch leaves
    keep the leading agent dim and are consumed sharded ``P(axis_name)``.
    With ``model_axis`` naming a second mesh axis of size M > 1, the D dim
    is additionally column-sharded over it (state placed via
    ``shard_flat_state(..., model_axis=...)``) and each agent replica runs
    tensor-sharded — the 2-D engine.
    """
    espec = engine.parse_engine_spec(
        cfg, layout="flat", n_shards=agent_axis_size(mesh, axis_name),
        axis_name=axis_name,
        n_model_shards=(dict(mesh.shape).get(model_axis, 1)
                        if model_axis is not None else 1),
        model_axis=model_axis if model_axis is not None else "model")
    if model_axis is not None:
        _validate_model_axis(cfg, spec, mesh, model_axis)
    return engine.make_engine_step(espec, grad_fn, lr_fn, flat_spec=spec,
                                   mesh=mesh, optimizer=optimizer,
                                   block_d=block_d, donate=donate, jit=jit)


def _lower_sharded_round(cfg: FedDecConfig, spec: FlatSpec,
                         grad_fn: GradFn, lr_fn: LrFn,
                         mesh: jax.sharding.Mesh, *,
                         axis_name: str | tuple[str, ...] = "agents",
                         optimizer=None, block_d: int | None = None,
                         donate: bool = True, jit: bool = True,
                         unroll: int = 1, model_axis: str | None = None):
    ax = _resolve_axis(mesh, axis_name)
    n_shards = _validate(cfg, mesh, ax)
    if model_axis is not None:
        m = _validate_model_axis(cfg, spec, mesh, model_axis)
        if m > 1:
            return _lower_sharded_round_2d(
                cfg, spec, grad_fn, lr_fn, mesh, ax, n_shards, model_axis,
                optimizer, block_d, donate, jit, unroll)
    per_shard = _build_per_shard_step(cfg, spec, grad_fn, lr_fn, ax,
                                      n_shards, optimizer, block_d)
    opt_specs = _opt_specs(optimizer, spec, cfg.n_agents, ax)
    res_specs = () if cfg.gossip_compress == "none" \
        or cfg.gossip_impl == "none" else P(ax)
    metric_specs = {"loss": P(None), "eta": P(None)}

    def per_shard_round(x_blk, res_blk, opt_blk, t0, batches_blk, key):
        def body(carry, batch):
            x, res, opt, t = carry
            z, new_res, new_opt, metrics = per_shard(x, res, opt, t, batch,
                                                     key)
            return (z, new_res, new_opt, t + 1), metrics

        (x, res, opt, t), metrics = jax.lax.scan(
            body, (x_blk, res_blk, opt_blk, t0), batches_blk, unroll=unroll)
        return x, res, opt, t, metrics

    smapped = _shard_map(
        per_shard_round, mesh,
        in_specs=(P(ax), res_specs, opt_specs, P(), P(None, ax), P()),
        out_specs=(P(ax), res_specs, opt_specs, P(), metric_specs))

    def round_fn(state: FlatFedState, batches: Any, key: jax.Array):
        flat, res, opt, t, metrics = smapped(state.flat, state.residual,
                                             state.opt_state, state.step,
                                             batches, key)
        return FlatFedState(flat=flat, step=t, opt_state=opt,
                            residual=res), metrics

    return engine.finalize_executor(round_fn, donate=donate, jit=jit)


def make_sharded_feddec_round(cfg: FedDecConfig, spec: FlatSpec,
                              grad_fn: GradFn, lr_fn: LrFn,
                              mesh: jax.sharding.Mesh, *,
                              axis_name: str | tuple[str, ...] = "agents",
                              optimizer=None, block_d: int | None = None,
                              donate: bool = True, jit: bool = True,
                              unroll: int = 1,
                              model_axis: str | None = None):
    """The fused sharded executor: H steps per compiled call, one shard_map.

    Same contract as repro.core.flat.make_flat_feddec_round — batches carry
    a leading fused-step dim (consumed ``P(None, axis_name)``), W^t resamples
    per scanned step, metrics stack to (H,) — but the whole ``lax.scan`` runs
    *inside* a single ``shard_map``, so each device scans its own row block
    and the per-step collectives (psum_scatter / ppermute halo / server psum)
    are the only cross-device traffic in the round.

    With ``model_axis`` naming a second mesh axis of size M > 1 the 2-D
    engine lowers instead: the scan moves to the jit level around a
    partially-auto shard_map, the D dim is column-sharded over 'model'
    (per-device state ``n/A · D/M``), and the trajectory still matches the
    flat reference to 1e-5.
    """
    espec = engine.parse_engine_spec(
        cfg, layout="flat", n_shards=agent_axis_size(mesh, axis_name),
        axis_name=axis_name,
        n_model_shards=(dict(mesh.shape).get(model_axis, 1)
                        if model_axis is not None else 1),
        model_axis=model_axis if model_axis is not None else "model")
    if model_axis is not None:
        _validate_model_axis(cfg, spec, mesh, model_axis)
    return engine.make_engine_round(espec, grad_fn, lr_fn, flat_spec=spec,
                                    mesh=mesh, optimizer=optimizer,
                                    block_d=block_d, donate=donate, jit=jit,
                                    unroll=unroll)
