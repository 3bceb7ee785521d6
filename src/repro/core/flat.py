"""Flat-state FedDec engine: Algorithm 1 on one contiguous (n_agents, D) buffer.

The tree engine (repro.core.feddec) carries the stacked per-agent parameters
as a pytree and applies every Algorithm-1 op leaf-wise — paying per-leaf
dispatch inside the fused scan, per-leaf padding in the Pallas kernel, and a
per-leaf f32 upcast in the dense einsum.  This module ravels the whole state
**once** into a single contiguous ``(n_agents, D)`` buffer with a static
unravel spec, so each op of the hot loop becomes exactly one fused
whole-buffer pass:

  * local SGD / optimizer update —  one elementwise op over (n, D);
  * gossip  x_i ← Σ_j W_ij x_j   —  one (n, n) @ (n, D) contraction
    (``gossip_impl='dense'``), one Pallas streaming-kernel call with W
    VMEM-resident and the dtype cast fused (``'pallas'``), or one
    gather + segment_sum over the graph's CSR edge list (``'sparse'``,
    O(|E|·D) — the n≫64 regime the dense path cannot sustain);
  * server round                  —  one (n,)·(n, D) contraction + broadcast.

The pytree is reconstructed only at the ``grad_fn`` boundary (models consume
trees), via static-slice views that XLA folds into the surrounding
computation; gradients are re-ravelled the same way.  A flat-engine round
computes the same trajectory as the tree engine within 1e-5
(tests/test_flat_engine.py) — ``FlatSpec.unflatten ∘ flatten`` is exact, and
every whole-buffer op is the leaf-wise op with the leaf loop removed.

Mapping to the paper: the buffer's row ``flat[i]`` IS Algorithm 1's x_i / z_i
(agent i's full parameter vector, x_i ∈ ℝ^D), so Algorithm-1 lines read off
directly as matrix ops on the buffer: line 6 is ``W @ flat``, lines 8–10 are
``(c/K) @ flat`` broadcast back.  See docs/ALGORITHM.md.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compress as compress_lib
from repro.core import delta as delta_lib
from repro.core import engine
from repro.core import server as server_lib
from repro.core.feddec import FedDecConfig, FedState

__all__ = ["FlatSpec", "FlatFedState", "make_flat_spec",
           "make_flat_spec_from_stacked", "init_flat_state",
           "flatten_fedstate", "unflatten_fedstate",
           "make_flat_feddec_step", "make_flat_feddec_round",
           "resolve_flat_gossip"]

GradFn = Callable[[Any, Any, jax.Array], tuple[jax.Array, Any]]
LrFn = Callable[[jax.Array], jax.Array]

LANES = 128  # a TPU vreg's lane width: the (n, D) buffer's tile is (n, 128)


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static ravel/unravel spec: pytree ⇄ contiguous flat vector.

    Built once per (model × dtype); the slicing offsets are Python ints, so
    ``unflatten`` lowers to static slices + reshapes that XLA fuses into the
    consumer — reconstructing the tree view costs no extra memory pass.

    Attributes:
      treedef: pytree structure of the single-agent parameters.
      shapes/dtypes: per-leaf (no agent dim) shapes and original dtypes.
      offsets/sizes: per-leaf [offset, offset+size) spans in the flat vector.
      d: total flat length D = Σ sizes.
      dtype: the buffer dtype (all leaves are cast into it on flatten and
        back to their original dtype on unflatten).

    A leaf whose rows are not lane-aligned but whose per-agent size is
    (``lane_dense_leaves``) moves between its own shape and its (n, S)
    segment through ``_to_shape``'s lane-dense swap: a plain reshape of
    such a leaf makes XLA:TPU copy it one agent at a time, in a ``while``
    loop, into the buffer's (n, 128) tiles and back.
    """

    treedef: Any
    shapes: tuple
    dtypes: tuple
    offsets: tuple
    sizes: tuple
    d: int
    dtype: Any

    @property
    def num_leaves(self) -> int:
        return len(self.shapes)

    @property
    def lane_dense_leaves(self) -> tuple[int, ...]:
        """Indices of the leaves that move through the lane-dense swap:
        the last dimension is not a multiple of ``LANES``, the per-agent
        size is."""
        return tuple(i for i, (shape, s) in enumerate(zip(self.shapes,
                                                          self.sizes))
                     if shape and shape[-1] % LANES and not s % LANES)

    @property
    def lane_dense_share(self) -> float:
        """The lane-dense leaves' share of D."""
        return sum(self.sizes[i] for i in self.lane_dense_leaves) / self.d

    def _to_shape(self, i: int, x: jax.Array, shape: tuple) -> jax.Array:
        """Leaf ``i``'s stacked values ``x`` (n, ...) → shape (n, *shape).

        A lane-dense leaf is viewed as rows of 128 lanes, (n, S/128, 128),
        and swapped to (S/128, n, 128), the order of the buffer's (n, 128)
        tiles, behind an optimization barrier, then swapped back: XLA moves
        it in one relayout pass.  Without the barrier the two swaps cancel
        and the per-agent loop returns.  Values are moved, never computed.
        """
        n = x.shape[0]
        if i in self.lane_dense_leaves:
            v = jnp.swapaxes(x.reshape(n, -1, LANES), 0, 1)
            x = jnp.swapaxes(jax.lax.optimization_barrier(v), 0, 1)
        return x.reshape((n,) + shape)

    # -- single-agent (no leading n) ----------------------------------------

    def ravel(self, tree: Any) -> jax.Array:
        leaves = self.treedef.flatten_up_to(tree)
        return jnp.concatenate(
            [jnp.asarray(l).astype(self.dtype).reshape(-1) for l in leaves])

    def unravel(self, row: jax.Array, cast: bool = True) -> Any:
        parts = [
            row[o:o + s].reshape(shape).astype(dt if cast else row.dtype)
            for o, s, shape, dt in zip(self.offsets, self.sizes,
                                       self.shapes, self.dtypes)]
        return jax.tree.unflatten(self.treedef, parts)

    # -- stacked (leading agent dim) ----------------------------------------

    def flatten(self, stacked: Any, dtype=None) -> jax.Array:
        """Stacked pytree (every leaf (n, ...)) → (n, D) buffer.

        ``dtype`` overrides the buffer dtype (used for optimizer-state
        buffers, which stay f32 even when the parameter buffer is bf16).
        """
        leaves = self.treedef.flatten_up_to(stacked)
        dt = self.dtype if dtype is None else dtype
        with jax.named_scope("feddec.flatten"):
            return jnp.concatenate(
                [self._to_shape(i, jnp.asarray(l).astype(dt), (s,))
                 for i, (l, s) in enumerate(zip(leaves, self.sizes))],
                axis=1)

    def unflatten(self, buf: jax.Array, cast: bool = True) -> Any:
        """(n, D) buffer → stacked pytree of (n, ...) leaves.

        The leaves pass an optimization barrier, so each is materialised
        once instead of being fused, as a slice of the buffer, into its
        consumers: XLA:TPU spends minutes on column slices of the (n, D)
        buffer fused into the model's matmuls (the fused round of a
        2-layer, vocab-32768 tiny LM with 4 agents compiled for a v5e in
        200 s without the barrier and in 97 s with it, on a CPU host).
        """
        with jax.named_scope("feddec.unflatten"):
            parts = [
                self._to_shape(i, buf[:, o:o + s], shape)
                .astype(dt if cast else buf.dtype)
                for i, (o, s, shape, dt) in enumerate(zip(
                    self.offsets, self.sizes, self.shapes, self.dtypes))]
            parts = jax.lax.optimization_barrier(parts)
        return jax.tree.unflatten(self.treedef, parts)


def _spec_from_leaves(leaves, treedef, dtype) -> FlatSpec:
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    if dtype is None:
        dtype = jnp.result_type(*dtypes) if dtypes else jnp.float32
    sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
    offsets = tuple(int(o) for o in np.cumsum((0,) + sizes)[:-1])
    return FlatSpec(treedef=treedef, shapes=shapes, dtypes=dtypes,
                    offsets=offsets, sizes=sizes, d=int(sum(sizes)),
                    dtype=jnp.dtype(dtype))


def make_flat_spec(params_single: Any, dtype=None) -> FlatSpec:
    """Spec from a single-agent pytree (arrays or ShapeDtypeStructs).

    ``dtype`` defaults to the promoted dtype of all leaves (f32 params stay
    f32, pure-bf16 models keep a bf16 buffer — the exchange-compression
    regime; mixed trees promote).
    """
    leaves, treedef = jax.tree.flatten(params_single)
    return _spec_from_leaves(leaves, treedef, dtype)


def make_flat_spec_from_stacked(stacked: Any, dtype=None) -> FlatSpec:
    """Spec from a *stacked* pytree (leading agent dim stripped per leaf)."""
    leaves, treedef = jax.tree.flatten(stacked)
    struct = [jax.ShapeDtypeStruct(l.shape[1:], l.dtype) for l in leaves]
    return _spec_from_leaves(struct, treedef, dtype)


# ---------------------------------------------------------------------------
# Flat training state
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FlatFedState:
    """Flat-engine carried state: the (n_agents, D) buffer + step counter.

    ``flat[i]`` is Algorithm 1's z_i^t ∈ ℝ^D.  Optimizer state lives in
    buffers of the same layout (e.g. a momentum (n, D) buffer), so the local
    update is elementwise over contiguous memory.
    """

    flat: jax.Array      # (n_agents, D), spec.dtype
    step: jax.Array      # scalar int32, the paper's t (starts at 1)
    opt_state: Any = ()  # flat optimizer buffers (SGD: empty)
    residual: Any = ()   # (n, D) compressed-gossip EF residual, or ()


def init_flat_state(spec: FlatSpec, params_single: Any, n_agents: int,
                    optimizer=None, compress: str = "none",
                    delta: str = "none") -> FlatFedState:
    """z_i^1 = z^1 ∀i (Alg. 1 line 1), directly in the flat layout.

    ``compress != 'none'`` adds the zero-initialised (n, D) error-feedback
    residual buffer the compressed-gossip step carries (repro.core.compress);
    ``delta != 'none'`` carries the same residual for the delta-encoded
    exchange (repro.core.delta) — the two are mutually exclusive.
    """
    row = spec.ravel(params_single)
    flat = jnp.tile(row[None], (n_agents, 1))
    opt_state = optimizer.init(flat) if optimizer is not None else ()
    needs_res = (compress_lib.parse_compress(compress) is not None
                 or delta_lib.parse_delta(delta).kind != "none")
    residual = jnp.zeros((n_agents, spec.d), spec.dtype) if needs_res else ()
    return FlatFedState(flat=flat, step=jnp.asarray(1, dtype=jnp.int32),
                        opt_state=opt_state, residual=residual)


def _flatten_opt_state(spec: FlatSpec, opt_state: Any):
    """Tree-engine opt state → flat buffers.

    Moment buffers keep their own (f32) dtype rather than the parameter
    buffer's — matching what ``init_flat_state``'s ``optimizer.init(flat)``
    produces, so entering the flat engine mid-training and starting in it
    give the same trajectory even with a bf16 parameter buffer.

    Supports the repro.optim optimizers: stateless SGD (()), params-shaped
    trees (momentum), and the adamw dict ({'m','v','count'} with a per-agent
    count that is identical across agents by construction).
    """
    if isinstance(opt_state, tuple) and opt_state == ():
        return ()
    if jax.tree.structure(opt_state) == spec.treedef:
        dt = jnp.result_type(*jax.tree.leaves(opt_state))
        return spec.flatten(opt_state, dtype=dt)
    if isinstance(opt_state, dict) and set(opt_state) == {"m", "v", "count"}:
        def moment_dtype(tree):
            return jnp.result_type(*jax.tree.leaves(tree))
        return {"m": spec.flatten(opt_state["m"],
                                  dtype=moment_dtype(opt_state["m"])),
                "v": spec.flatten(opt_state["v"],
                                  dtype=moment_dtype(opt_state["v"])),
                "count": opt_state["count"][0]}
    raise ValueError(
        "cannot flatten this optimizer state layout; re-init with "
        "init_flat_state(spec, params_single, n, optimizer=...) instead")


def _unflatten_opt_state(spec: FlatSpec, opt_state: Any, n_agents: int):
    if isinstance(opt_state, tuple) and opt_state == ():
        return ()
    if isinstance(opt_state, dict) and set(opt_state) == {"m", "v", "count"}:
        return {"m": spec.unflatten(opt_state["m"], cast=False),
                "v": spec.unflatten(opt_state["v"], cast=False),
                "count": jnp.broadcast_to(opt_state["count"], (n_agents,))}
    return spec.unflatten(opt_state, cast=False)


def _no_residual(residual: Any) -> bool:
    """() is the 'no residual' sentinel; a *tuple-structured* residual tree
    (tuple/NamedTuple params) is real state and must not match."""
    return isinstance(residual, tuple) and residual == ()


def flatten_fedstate(spec: FlatSpec, state: FedState) -> FlatFedState:
    """Tree-engine FedState → FlatFedState (one-time ravel, e.g. at start)."""
    residual = () if _no_residual(state.residual) \
        else spec.flatten(state.residual)
    return FlatFedState(flat=spec.flatten(state.params), step=state.step,
                        opt_state=_flatten_opt_state(spec, state.opt_state),
                        residual=residual)


def unflatten_fedstate(spec: FlatSpec, fstate: FlatFedState) -> FedState:
    """FlatFedState → tree-engine FedState (e.g. for checkpointing/eval)."""
    n = fstate.flat.shape[0]
    residual = () if _no_residual(fstate.residual) \
        else spec.unflatten(fstate.residual, cast=False)
    return FedState(params=spec.unflatten(fstate.flat), step=fstate.step,
                    opt_state=_unflatten_opt_state(spec, fstate.opt_state, n),
                    residual=residual)


# ---------------------------------------------------------------------------
# Whole-buffer gossip dispatch
# ---------------------------------------------------------------------------


def resolve_flat_gossip(cfg: FedDecConfig,
                        block_d: int | None = None) -> Callable:
    """gossip_impl → a whole-buffer (w, (n, D)) -> (n, D) mixing fn.

    Compatibility shim over :func:`repro.core.engine.resolve_gossip`:
    'dense'  one einsum contraction;
    'pallas' one kernels.ops.gossip_mix call (W VMEM-resident, cast fused);
    'sparse' neighbour-only mix over the static edge structure — the
             edge-blocked Pallas kernel on TPU, ELL/CSR gather off it;
    'none'   identity (FedAvg).
    """
    return engine.resolve_gossip(cfg, "flat", block_d=block_d)


# ---------------------------------------------------------------------------
# Executors (mirror repro.core.feddec's, on the flat carry)
# ---------------------------------------------------------------------------


def _fuse_kind(cfg: FedDecConfig, optimizer, custom_gossip: bool):
    """The optimizer kind the fused update+mix kernels can replicate, or
    None when this configuration must keep the unfused two-op path.

    Fusable: sgd (optimizer=None or kind 'sgd') and momentum, on the
    resolved dense/pallas/sparse mixes.  Everything else — adamw / custom
    optimizers (the bias-corrected rescale needs whole-state context), a
    caller-supplied gossip_fn (opaque), impl 'none' (no mix to fuse), or a
    sparse graph too skewed for the ELL layout — falls back, bit-identical
    to the flag being off.
    """
    if custom_gossip or cfg.gossip_impl not in ("dense", "pallas", "sparse"):
        return None
    kind = "sgd" if optimizer is None else getattr(optimizer, "kind",
                                                   "custom")
    if kind not in ("sgd", "momentum"):
        return None
    if cfg.gossip_impl == "sparse":
        from repro.core import gossip as gossip_lib
        graph = cfg.mixing.graph
        max_deg = int(graph.degrees.max()) if graph.n else 0
        if not 0 < max_deg <= gossip_lib.ELL_MAX_DEG:
            return None
    return kind


def _make_fused_flat_op(cfg: FedDecConfig, spec: FlatSpec, grads_of,
                        local_update, optimizer, compressor,
                        custom_gossip: bool):
    """The flat engine's fused lines-5–6 op (EngineOps.fused_update_gossip).

    Uncompressed: one kernels/update_mix.py pass — the post-update iterate
    never touches HBM.  Codec active: the update and the whole-row encode
    stay on XLA (shared with every other engine, so payloads stay
    bit-identical) and the fused EF kernel collapses mix + diag correction
    + residual into one pass.  Returns None when ineligible (the caller
    keeps the unfused body).
    """
    kind = _fuse_kind(cfg, optimizer, custom_gossip)
    if kind is None:
        return None
    from repro.kernels import ops as kernel_ops
    hyper = optimizer.hyperparams() if kind == "momentum" else {}
    beta = hyper.get("beta")
    nesterov = bool(hyper.get("nesterov", False))
    sparse = cfg.gossip_impl == "sparse"
    if compressor is not None:
        ef_kernel = kernel_ops.make_sparse_ef_mix_pallas(cfg.mixing.graph) \
            if sparse else kernel_ops.ef_mix
        n_agents = cfg.n_agents

        def fused(w, state, batch, key_grad, eta, residual, key_c):
            losses, x_half, new_opt = local_update(state, batch, key_grad,
                                                   eta)
            keys = jax.random.split(key_c, n_agents) \
                if compressor.needs_key else None
            u = x_half + residual
            payload = compressor.encode(keys, u)
            s = compressor.decode(payload, u.dtype, u.shape[1])
            y, new_res = ef_kernel(w, x_half, s, u)
            return losses, y, new_opt, new_res

        return fused

    if sparse:
        fused_mix = kernel_ops.make_sparse_update_mix_pallas(
            cfg.mixing.graph, beta=beta, nesterov=nesterov)
    elif kind == "momentum":
        def fused_mix(w, x, g, eta, m):
            return kernel_ops.update_mix(w, x, g, eta, m=m, beta=beta,
                                         nesterov=nesterov)
    else:
        fused_mix = kernel_ops.update_mix

    def fused(w, state, batch, key_grad, eta, residual, key_c):
        losses, g_flat = grads_of(state, batch, key_grad)
        if kind == "sgd":
            y = fused_mix(w, state.flat, g_flat, eta)
            return losses, y, state.opt_state, residual
        y, new_m = fused_mix(w, state.flat, g_flat, eta, state.opt_state)
        return losses, y, new_m, residual

    return fused


def _flat_ops(cfg: FedDecConfig, spec: FlatSpec, grad_fn: GradFn,
              lr_fn: LrFn, gossip_fn, optimizer,
              delta_base=None, fuse_update_mix: bool = False
              ) -> engine.EngineOps:
    """The flat engine's vtable for the shared Algorithm-1 body."""
    custom_gossip = gossip_fn is not None
    if gossip_fn is None:
        gossip_fn = engine.resolve_gossip(cfg, "flat")
    n_agents = cfg.n_agents
    # whole-buffer compressed exchange with error feedback; the int8 ×
    # 'pallas' combination runs the fused quantize→mix→dequantize kernel
    # (kernels/compress_mix.py) instead of three whole-buffer passes
    compressor = compress_lib.parse_compress(cfg.gossip_compress) \
        if cfg.gossip_impl != "none" else None
    # delta-parameterized exchange: the wire carries encoded deltas against
    # a shared base row, through the identical EF wrapper (delta='full' is
    # the lossless anchor — bit-identical to the uncompressed path)
    if compressor is None and cfg.gossip_impl != "none" \
            and delta_lib.parse_delta(cfg.delta).kind != "none":
        base = jnp.zeros((spec.d,), spec.dtype) if delta_base is None \
            else jnp.asarray(delta_base, spec.dtype).reshape(-1)
        if base.shape[0] != spec.d:
            raise ValueError(f"delta_base has D={base.shape[0]}, flat spec "
                             f"has D={spec.d}")
        compressor = delta_lib.make_delta_codec(cfg.delta, base)
    ef_gossip = None
    if compressor is not None:
        ef_gossip = compress_lib.make_flat_ef_gossip(
            compressor, gossip_fn, n_agents,
            fused_int8_pallas=cfg.gossip_impl == "pallas"
            and not custom_gossip)

    def grads_of(state: FlatFedState, batch: Any, key_grad):
        # line 4: tree view for the model, flat buffer for everything else
        params = spec.unflatten(state.flat)
        agent_keys = jax.random.split(key_grad, n_agents)
        losses, grads = jax.vmap(grad_fn)(params, batch, agent_keys)
        return losses, spec.flatten(grads)

    def local_update(state: FlatFedState, batch: Any, key_grad, eta):
        losses, g_flat = grads_of(state, batch, key_grad)
        if optimizer is None:  # plain SGD: one elementwise pass over (n, D)
            return losses, state.flat - eta.astype(spec.dtype) * g_flat, \
                state.opt_state
        x_half, new_opt = optimizer.update(state.flat, g_flat,
                                           state.opt_state, eta)
        return losses, x_half, new_opt

    fused_update_gossip = None
    if fuse_update_mix:
        fused_update_gossip = _make_fused_flat_op(
            cfg, spec, grads_of, local_update, optimizer, compressor,
            custom_gossip)

    def server(key_server, x_next, t):
        if not cfg.server_enabled:
            return x_next
        return jax.lax.cond(
            (t + 1) % cfg.h == 0,
            lambda x: server_lib.server_round_flat(key_server, x, cfg.k),
            lambda x: x,
            x_next)

    def finish(state, z_next, new_opt, new_res, t, losses, eta):
        new_state = FlatFedState(flat=z_next, step=t + 1, opt_state=new_opt,
                                 residual=new_res)
        return new_state, {"loss": jnp.mean(losses), "eta": eta}

    return engine.EngineOps(
        get_step=lambda s: s.step,
        derive_keys=lambda key, t: jax.random.split(
            jax.random.fold_in(key, t), 3),
        eta_fn=lr_fn,
        sample_w=cfg.mixing.sample,
        local_update=local_update,
        gossip=gossip_fn,
        get_residual=lambda s: s.residual,
        server=server,
        finish=finish,
        fold_codec=None if compressor is None else (
            lambda key_w: jax.random.fold_in(key_w, 1)),
        ef_gossip=ef_gossip,
        fused_update_gossip=fused_update_gossip)


def _build_flat_step_body(cfg: FedDecConfig, spec: FlatSpec, grad_fn: GradFn,
                          lr_fn: LrFn, gossip_fn, optimizer,
                          delta_base=None, fuse_update_mix: bool = False):
    """Algorithm-1 body on the flat carry; unflattens only around grad_fn."""
    return engine.build_step_body(
        _flat_ops(cfg, spec, grad_fn, lr_fn, gossip_fn, optimizer,
                  delta_base=delta_base, fuse_update_mix=fuse_update_mix))


def _lower_flat_step(cfg: FedDecConfig, spec: FlatSpec, grad_fn: GradFn,
                     lr_fn: LrFn, *, gossip_fn=None, optimizer=None,
                     donate: bool = True, jit: bool = True,
                     delta_base=None, fuse_update_mix: bool = False):
    step = _build_flat_step_body(cfg, spec, grad_fn, lr_fn, gossip_fn,
                                 optimizer, delta_base=delta_base,
                                 fuse_update_mix=fuse_update_mix)
    return engine.finalize_executor(step, donate=donate, jit=jit)


def _lower_flat_round(cfg: FedDecConfig, spec: FlatSpec, grad_fn: GradFn,
                      lr_fn: LrFn, *, gossip_fn=None, optimizer=None,
                      metrics_fn=None, donate: bool = True, jit: bool = True,
                      unroll: int = 1, delta_base=None,
                      fuse_update_mix: bool = False):
    step = _build_flat_step_body(cfg, spec, grad_fn, lr_fn, gossip_fn,
                                 optimizer, delta_base=delta_base,
                                 fuse_update_mix=fuse_update_mix)
    round_fn = engine.make_scan_round(step, metrics_fn=metrics_fn,
                                      unroll=unroll)
    return engine.finalize_executor(round_fn, donate=donate, jit=jit)


def make_flat_feddec_step(cfg: FedDecConfig, spec: FlatSpec, grad_fn: GradFn,
                          lr_fn: LrFn, gossip_fn=None, optimizer=None,
                          donate: bool = True, jit: bool = True,
                          delta_base=None, fuse_update_mix: bool = False):
    """One-iteration flat executor: step(state, batch, key) like the tree
    engine's make_feddec_step, carrying FlatFedState."""
    espec = engine.parse_engine_spec(cfg, layout="flat",
                                     fuse_update_mix=fuse_update_mix)
    return engine.make_engine_step(espec, grad_fn, lr_fn, flat_spec=spec,
                                   gossip_fn=gossip_fn, optimizer=optimizer,
                                   donate=donate, jit=jit,
                                   delta_base=delta_base)


def make_flat_feddec_round(cfg: FedDecConfig, spec: FlatSpec, grad_fn: GradFn,
                           lr_fn: LrFn, gossip_fn=None, optimizer=None,
                           metrics_fn: Callable[[FlatFedState], dict]
                           | None = None,
                           donate: bool = True, jit: bool = True,
                           unroll: int = 1, delta_base=None,
                           fuse_update_mix: bool = False):
    """The fused flat executor: H steps per compiled call, flat carry.

    Same contract as repro.core.feddec.make_feddec_round — batches carry a
    leading fused-step dim, W^t resamples per scanned step, metrics stack to
    (H,) — but the scan carry is the single (n, D) buffer (+ flat optimizer
    buffers), so the scan body is a handful of whole-buffer ops instead of a
    tree of per-leaf ones.  ``metrics_fn`` receives the post-step
    FlatFedState; use ``spec.unflatten(state.flat)`` inside it for
    tree-shaped diagnostics.
    """
    espec = engine.parse_engine_spec(cfg, layout="flat",
                                     fuse_update_mix=fuse_update_mix)
    return engine.make_engine_round(espec, grad_fn, lr_fn, flat_spec=spec,
                                    gossip_fn=gossip_fn, optimizer=optimizer,
                                    metrics_fn=metrics_fn, donate=donate,
                                    jit=jit, unroll=unroll,
                                    delta_base=delta_base)
