"""Unified FedDec executor: every engine is one EngineSpec lowering.

The repo grew four engines for Algorithm 1 — tree (repro.core.feddec), flat
(repro.core.flat), device-sharded (repro.core.sharded) and batched-sweep
(repro.core.sweep) — that each re-implemented the same step skeleton:

    derive per-step keys → η_t → sample W^t → per-agent local update
    → (compress/EF) gossip mix → masked periodic server round.

This module is the single source of truth for that skeleton and for the
configuration lattice that selects a lowering:

  * :class:`EngineSpec` — ``(layout × run-batch × mesh shards × codec ×
    gossip-impl)``.  ``layout`` picks the state carry ('tree' pytree vs
    'flat' (n, D) buffer); ``configs`` holds one FedDecConfig per run (R > 1
    batches a sweep lattice); ``n_shards`` > 1 block-shards the agent axis
    of the flat buffer over a mesh.  :func:`parse_engine_spec` validates the
    combination (tree is single-run/single-device; sweep lattices validate
    through ``sweep.make_sweep_plan``).
  * :class:`EngineOps` + :func:`build_step_body` — the ONE shared
    Algorithm-1 scan body.  Each engine contributes a small vtable of ops
    (how to derive keys, run the local update, mix, fire the server round,
    rebuild its carry); the body wires them in the canonical order, so the
    four step implementations cannot drift again.
  * :func:`make_scan_round` — the shared fused-round wrapper (scan +
    optional per-step ``metrics_fn`` merge + optional per-step keys),
    previously copy-pasted across three modules.
  * :func:`resolve_gossip` — THE gossip_impl dispatcher for every layout
    ('tree' leaf-wise, 'flat' whole-buffer, 'sweep' whole-lattice, 'sharded'
    per-shard mixer).  Unknown impls raise the same ValueError everywhere
    (:func:`unknown_gossip_impl`), including from ``FedDecConfig`` itself.
  * :func:`make_engine_step` / :func:`make_engine_round` — lower a spec to
    an executor.  The public per-engine constructors
    (``make_feddec_round``, ``make_flat_feddec_round``,
    ``make_sharded_feddec_round``, ``make_sweep_feddec_round``) are
    compatibility shims over this dispatch.

and the composition the split engines could not express:

  * :func:`make_sharded_sweep_round` — ``R`` sweep runs × ``s`` agent
    shards in ONE program.  The whole fig4 lattice runs as a
    ``(R, n_agents/s per device, D)`` carry: per-run topologies / H / step
    budgets batch over the run axis exactly as in the sweep engine, while
    gossip runs per shard — the dense path contracts each device's column
    block of every run's W^t and ``psum_scatter``s the (R, n, D) partials
    over the agent axis; the sparse/pallas path ``ppermute``s (R, n_local,
    D) halo blocks over the *union* quotient graph of the lattice (per-run
    W entries are zero off their own support, so sharing one halo schedule
    is exact).  Compressed gossip ppermutes the *encoded* per-run payload.
    Every run slice matches the single-run flat engine to ≤ 1e-5
    (tests/conformance).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import compress as compress_lib
from repro.core import gossip as gossip_lib
from repro.core import server as server_lib
from repro.core import topology as topo

__all__ = ["GOSSIP_IMPLS", "LAYOUTS", "EngineSpec", "EngineOps",
           "parse_engine_spec", "build_step_body", "make_scan_round",
           "finalize_executor", "resolve_gossip", "check_gossip_impl",
           "unknown_gossip_impl", "model_axis_conflict",
           "make_engine_step", "make_engine_round",
           "make_sharded_sweep_step", "make_sharded_sweep_round",
           "shard_sweep_state", "sweep_state_specs",
           "make_population_round"]

GradFn = Callable[[Any, Any, jax.Array], tuple[jax.Array, Any]]
LrFn = Callable[[jax.Array], jax.Array]

GOSSIP_IMPLS = ("dense", "none", "pallas", "sparse")
LAYOUTS = ("tree", "flat")

_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# gossip_impl validation + the one dispatcher (satellite: the four resolvers
# used to drift on error behaviour)
# ---------------------------------------------------------------------------


def unknown_gossip_impl(impl) -> ValueError:
    """THE unknown-gossip_impl error — identical from every entry point."""
    hint = (" (the mesh ppermute path is not a gossip_impl: build it "
            "with gossip.make_permute_gossip and pass gossip_fn=...)"
            if impl == "permute" else "")
    return ValueError(
        f"unknown gossip_impl {impl!r}; choose from "
        f"{'|'.join(GOSSIP_IMPLS)}{hint}")


def check_gossip_impl(impl: str) -> str:
    if impl not in GOSSIP_IMPLS:
        raise unknown_gossip_impl(impl)
    return impl


def model_axis_conflict(feature: str) -> ValueError:
    """THE model-axis incompatibility error — identical from every entry
    point (parse_engine_spec, the sharded constructors, launch/train.py),
    so incoherent ``--mesh-model`` combinations fail at validation time
    with one canonical message instead of deep inside shard_map."""
    return ValueError(
        f"model-axis sharding (n_model_shards > 1 / --mesh-model) does "
        f"not compose with {feature}; use n_model_shards=1")


def resolve_gossip(source, layout: str = "flat", *, block_d: int | None = None,
                   axis_name=None, n_shards: int | None = None) -> Callable:
    """gossip_impl → the mixing fn for one engine layout.

    ``source`` is a FedDecConfig (layouts 'tree' / 'flat' / 'sharded') or a
    SweepPlan (layout 'sweep') — anything with ``.gossip_impl`` plus the
    layout's topology fields.  Layouts:

    'tree'     (w, stacked-pytree) -> pytree — leaf-wise ops;
    'flat'     (w, (n, D)) -> (n, D) — whole-buffer ops;
    'sweep'    (w (R, n, n), x (R, n, D)) -> (R, n, D) — whole-lattice ops;
    'sharded'  per-shard mix(w, x_blk, me) -> y_blk (requires ``axis_name``
               and ``n_shards``) — psum_scatter / ppermute-halo collectives.

    Every impl table is the same: 'dense' einsum, 'pallas' streaming kernel,
    'sparse' static-edge-structure mix, 'none' identity (FedAvg).  Unknown
    impls raise :func:`unknown_gossip_impl` — the same error the config
    constructor raises, from every layout.
    """
    impl = source.gossip_impl

    if layout == "tree":
        if impl == "none":
            return lambda w, x: x
        if impl == "dense":
            return gossip_lib.gossip_mix_dense
        if impl == "pallas":
            from repro.kernels import ops as kernel_ops
            return kernel_ops.gossip_mix_tree
        if impl == "sparse":
            return gossip_lib.make_sparse_gossip_tree(source.mixing.graph)
        raise unknown_gossip_impl(impl)

    if layout == "flat":
        if impl == "none":
            return lambda w, x: x
        if impl == "dense":
            def mix(w: jax.Array, x: jax.Array) -> jax.Array:
                return jnp.einsum("ij,jd->id", w.astype(x.dtype), x,
                                  precision=_HIGHEST)
            return mix
        if impl == "pallas":
            from repro.kernels import ops as kernel_ops
            if block_d is None:
                return kernel_ops.gossip_mix
            return lambda w, x: kernel_ops.gossip_mix(w, x, block_d=block_d)
        if impl == "sparse":
            from repro.kernels import ops as kernel_ops
            graph = source.mixing.graph
            max_deg = int(graph.degrees.max()) if graph.n else 0
            # the kernel pads rows to max_deg (ELL), so it only makes sense
            # in the low/even-degree regime; skewed graphs keep the CSR
            # gather
            if kernel_ops.on_tpu() and 0 < max_deg <= gossip_lib.ELL_MAX_DEG:
                return kernel_ops.make_sparse_gossip_pallas(graph)
            return gossip_lib.make_sparse_gossip(graph)
        raise unknown_gossip_impl(impl)

    if layout == "sweep":
        if impl == "none":
            return lambda w, x: x
        if impl == "dense":
            def mix(w: jax.Array, x: jax.Array) -> jax.Array:
                return jnp.einsum("rij,rjd->rid", w.astype(x.dtype), x,
                                  precision=_HIGHEST)
            return mix
        if impl == "pallas":
            from repro.kernels import ops as kernel_ops
            if block_d is None:
                return kernel_ops.gossip_mix_batched
            return lambda w, x: kernel_ops.gossip_mix_batched(
                w, x, block_d=block_d)
        if impl == "sparse":
            from repro.kernels import ops as kernel_ops
            graphs = source.graphs
            max_deg = gossip_lib.lattice_max_degree(graphs)
            if kernel_ops.on_tpu() and 0 < max_deg <= gossip_lib.ELL_MAX_DEG:
                kw = {} if block_d is None else {"block_d": block_d}
                return kernel_ops.make_sparse_gossip_batched_pallas(graphs,
                                                                    **kw)
            return gossip_lib.make_sparse_gossip_batched(graphs)
        raise unknown_gossip_impl(impl)

    if layout == "sharded":
        if axis_name is None or n_shards is None:
            raise ValueError("layout 'sharded' needs axis_name and n_shards")
        from repro.core import sharded as sharded_lib
        return sharded_lib._make_shard_mixer(source, axis_name, n_shards,
                                             block_d=block_d)

    raise ValueError(f"unknown engine layout {layout!r}; choose from "
                     f"{'|'.join(LAYOUTS)}|sweep|sharded")


# ---------------------------------------------------------------------------
# The ONE Algorithm-1 step body
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineOps:
    """Per-engine vtable consumed by :func:`build_step_body`.

    Each engine builds one of these (closing over its config / spec /
    optimizer) and gets the canonical Algorithm-1 step back.  ``state`` is
    whatever the engine carries (FedState, FlatFedState, SweepFedState, or
    a per-shard carry tuple); the body never inspects it.

    Fields (Algorithm-1 lines in parentheses):
      get_step:     state -> t (the carried step counter(s)).
      derive_keys:  (key, t) -> (key_w, key_grad, key_server) — the
                    fold_in(key, t) + 3-split every engine shares.
      fold_codec:   key_w -> key_c, or None when no codec runs.  Derived
                    (never split) so uncompressed streams stay bit-identical.
      eta_fn:       t -> η_t (line 5's stepsize).
      sample_w:     key_w -> W^t (line 3).
      local_update: (state, batch, key_grad, eta) ->
                    (losses, x_half, new_opt) (lines 4–5).
      gossip:       (w, x_half) -> x_next (line 6, uncompressed).
      ef_gossip:    (w, x_half, residual, key_c) -> (x_next, new_residual)
                    (line 6 with compress/error feedback), or None.
      get_residual: state -> carried EF residual (ignored under ef_gossip
                    = None except to pass through unchanged).
      server:       (key_server, x_next, t) -> z_next (lines 7–12: the
                    masked/cond periodic server round — identity when
                    server_enabled is False).
      finish:       (state, z_next, new_opt, new_res, t, losses, eta) ->
                    (new_state, metrics) — rebuild the carry, advance t,
                    apply any freeze masks, assemble metrics.
      fused_update_gossip: (w, state, batch, key_grad, eta, residual,
                    key_c) -> (losses, x_next, new_opt, new_res), or None.
                    When set it REPLACES the local_update + gossip /
                    ef_gossip pair with one fused lines-5–6 op (the
                    update+mix megakernels of kernels/update_mix.py) —
                    same contract, one buffer pass.  Engines set it only
                    when the fused path reproduces the unfused numerics
                    (sgd/momentum; adamw keeps the two-op path).
    """

    get_step: Callable
    derive_keys: Callable
    eta_fn: Callable
    sample_w: Callable
    local_update: Callable
    gossip: Callable
    get_residual: Callable
    server: Callable
    finish: Callable
    fold_codec: Callable | None = None
    ef_gossip: Callable | None = None
    fused_update_gossip: Callable | None = None


def build_step_body(ops: EngineOps):
    """Assemble the shared Algorithm-1 step from an engine's ops.

    This is the only place the step order lives: key derivation → η_t →
    line 3 (sample W) → lines 4–5 (local update) → line 6 (gossip, EF
    branch when a codec is configured) → lines 7–12 (server) → carry
    rebuild.  All four engines — and the sharded-sweep composition — run
    exactly this body.

    Each phase runs under a ``feddec.<phase>`` named scope (``sample_w``,
    ``update``, ``mix``, ``update_mix``, ``server``), which reaches every
    compiled instruction's ``op_name``; an instruction's phase is the last
    ``feddec.*`` component of its ``op_name``, so the nested scopes of
    ``FlatSpec.flatten``/``unflatten`` and ``Model.grad_fn`` name their
    own ops.  Scopes are metadata: the program is the same without them.
    """
    def step(state, batch, key):
        t = ops.get_step(state)
        key_w, key_grad, key_server = ops.derive_keys(key, t)
        # derived (not split) so key_w/key_grad/key_server — and with
        # them every uncompressed trajectory — stay bit-identical
        key_c = ops.fold_codec(key_w) if ops.fold_codec is not None else None
        eta = ops.eta_fn(t)

        # line 3: sample W^t
        with jax.named_scope("feddec.sample_w"):
            w = ops.sample_w(key_w)

        if ops.fused_update_gossip is not None:
            # lines 4–6 in one buffer pass (kernels/update_mix.py)
            with jax.named_scope("feddec.update_mix"):
                losses, x_next, new_opt, new_res = ops.fused_update_gossip(
                    w, state, batch, key_grad, eta, ops.get_residual(state),
                    key_c)
        else:
            # lines 4–5: per-agent stochastic gradient + local update
            with jax.named_scope("feddec.update"):
                losses, x_half, new_opt = ops.local_update(state, batch,
                                                           key_grad, eta)

            # line 6: gossip averaging (compressed payload + EF residual
            # when a codec is configured)
            with jax.named_scope("feddec.mix"):
                if ops.ef_gossip is None:
                    x_next = ops.gossip(w, x_half)
                    new_res = ops.get_residual(state)
                else:
                    x_next, new_res = ops.ef_gossip(
                        w, x_half, ops.get_residual(state), key_c)

        # lines 7–12: periodic server round (partial participation)
        with jax.named_scope("feddec.server"):
            z_next = ops.server(key_server, x_next, t)

        return ops.finish(state, z_next, new_opt, new_res, t, losses, eta)

    return step


def make_scan_round(step, *, metrics_fn=None, per_step_keys: bool = False,
                    unroll: int = 1):
    """The shared fused-round wrapper: scan ``step`` over stacked batches.

    ``round_fn(state, batches, key)`` scans the leading axis of ``batches``;
    per-step metrics stack along it.  ``metrics_fn`` (state -> dict) is
    evaluated on each post-step state and merged into that step's metrics.
    ``per_step_keys=True`` scans ``key`` alongside the batches (leading axis
    T) instead of closing over one key.
    """
    def round_fn(state, batches, key):
        def body(carry, xs):
            batch, kk = xs if per_step_keys else (xs, key)
            new_state, metrics = step(carry, batch, kk)
            if metrics_fn is not None:
                metrics = {**metrics, **metrics_fn(new_state)}
            return new_state, metrics

        xs = (batches, key) if per_step_keys else batches
        return jax.lax.scan(body, state, xs, unroll=unroll)

    return round_fn


def finalize_executor(fn, donate: bool = True, jit: bool = True):
    """Shared jit/donation policy of every executor constructor."""
    if not jit:
        return fn
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def make_population_round(spec, flat_spec, grad_fn: GradFn, lr_fn: LrFn,
                          **kwargs):
    """The population engine's cohort round, through the executor surface.

    ``spec`` is a :class:`repro.core.population.PopulationSpec`; the result
    is ``round_fn(state, batches, key, mix)`` — the same fused Algorithm-1
    scan body every layout runs (:func:`build_step_body`), with the mixing
    op swapped for the per-round traced cohort-subgraph tables.  The
    host↔device streaming driver lives in
    :class:`repro.core.population.PopulationEngine`.
    """
    from repro.core import population as population_lib
    return population_lib.make_cohort_round(spec, flat_spec, grad_fn, lr_fn,
                                            **kwargs)


# ---------------------------------------------------------------------------
# EngineSpec: the configuration lattice
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """One point of the (layout × run-batch × mesh × codec × impl) lattice.

    Attributes:
      configs: one FedDecConfig per run.  len == 1 is a single run; len > 1
        is a sweep lattice (validated via ``sweep.make_sweep_plan`` —
        shared n_agents/K/server/codec, at most one non-'none' impl).
      layout: 'tree' (pytree state carry, single run, no sharding) or
        'flat' (contiguous (n, D) buffer — the layout runs/shards batch
        over).
      n_shards: agent-axis shards (1 = single device).  Lowering with
        n_shards > 1 requires a mesh whose ``axis_name`` axis has this size.
      axis_name: mesh axis (or axes tuple) carrying the agent sharding.
      n_model_shards: model-axis shards per agent replica (1 = each row
        whole on its device).  > 1 lowers the 2-D mesh engine: the flat
        buffer is additionally column-sharded over ``model_axis``, gossip /
        server collectives stay over ``axis_name`` only, and the model
        compute runs tensor-sharded over ``model_axis``.  Single-run flat
        only (tree / sweep / delta combinations raise
        :func:`model_axis_conflict`).
      model_axis: mesh axis carrying the model (tensor) sharding.
      t_steps: optional per-run step budgets (sweep freeze masking).
      force_run_axis: keep the run axis even for a single run (the sweep
        engine's own public API lowers R = 1 plans this way so its carry
        stays a SweepFedState).
      delta: the delta-parameterization axis (mirrors the shared
        ``FedDecConfig.delta``): 'none' | 'full' | 'topk:K' | 'lowrank:R'.
        Non-'none' lowers on the single-run, single-device flat engine
        (agents exchange encoded deltas against a shared base row —
        repro.core.delta); the population engine consumes the same codecs
        host-side via DeltaStore.
      fuse_update_mix: run lines 5–6 as one fused buffer pass (the
        update+mix megakernels of kernels/update_mix.py) on the flat /
        sweep lowerings.  Trajectories match the unfused body to ≤ 1e-5;
        optimizers the kernels cannot replicate (adamw, custom) and custom
        gossip_fn overrides fall back to the two-op path automatically.
        Tree layouts and agent-sharded meshes reject the flag at parse
        time (the sharded engine overlaps its halo with interior compute
        instead — core/sharded.py).
    """

    configs: tuple
    layout: str = "flat"
    n_shards: int = 1
    axis_name: Any = "agents"
    t_steps: tuple | None = None
    force_run_axis: bool = False
    delta: str = "none"
    n_model_shards: int = 1
    model_axis: Any = "model"
    fuse_update_mix: bool = False

    @property
    def cfg(self):
        return self.configs[0]

    @property
    def r_runs(self) -> int:
        return len(self.configs)

    @property
    def has_run_axis(self) -> bool:
        return self.r_runs > 1 or self.force_run_axis

    @property
    def is_sharded(self) -> bool:
        return self.n_shards > 1

    @property
    def is_model_sharded(self) -> bool:
        return self.n_model_shards > 1

    def plan(self):
        """The validated SweepPlan of this spec's run lattice."""
        from repro.core import sweep as sweep_lib
        t = None if self.t_steps is None else np.asarray(self.t_steps,
                                                         np.int32)
        return sweep_lib.make_sweep_plan(self.configs, t_steps=t)


def parse_engine_spec(configs, layout: str = "flat", n_shards: int = 1,
                      axis_name="agents", t_steps=None,
                      force_run_axis: bool = False, n_model_shards: int = 1,
                      model_axis="model",
                      fuse_update_mix: bool = False) -> EngineSpec:
    """Validate and freeze an EngineSpec.

    ``configs`` may be a single FedDecConfig or an iterable of them.  Raises
    ValueError on any invalid combination: unknown layout, a tree-layout
    sweep/sharding, shards not dividing n_agents, a lattice the sweep
    plan rejects (mismatched n_agents/K/server/codec, > 1 non-'none' impl,
    malformed t_steps), a model-sharded spec combined with tree / sweep /
    delta / topk compression (:func:`model_axis_conflict`), or
    ``fuse_update_mix`` on a layout without a flat single-device buffer
    (tree / agent-sharded / model-sharded).
    """
    if hasattr(configs, "gossip_impl"):  # a single config
        configs = (configs,)
    configs = tuple(configs)
    if not configs:
        raise ValueError("engine spec needs at least one run config")
    if layout not in LAYOUTS:
        raise ValueError(f"unknown engine layout {layout!r}; choose from "
                         f"{'|'.join(LAYOUTS)}")
    if layout == "tree":
        if len(configs) > 1 or force_run_axis:
            raise ValueError("layout 'tree' lowers a single run; use "
                             "layout='flat' for sweep lattices")
        if n_shards > 1:
            raise ValueError("layout 'tree' does not shard the agent axis; "
                             "use layout='flat' with a mesh")
    n = configs[0].n_agents
    if n_shards < 1 or n % n_shards:
        raise ValueError(f"n_agents={n} must be divisible by the agent axis "
                         f"size {n_shards} (block-sharded rows)")
    if t_steps is not None:
        t_steps = tuple(int(t) for t in np.asarray(t_steps).reshape(-1))
    delta = getattr(configs[0], "delta", "none")
    if any(getattr(c, "delta", "none") != delta for c in configs):
        raise ValueError("all runs of an engine lattice must share one "
                         "delta parameterization")
    if delta != "none":
        if layout == "tree":
            raise ValueError(
                "delta parameterization needs the flat (n, D) layout — the "
                "base row and encoded payloads are whole-buffer objects; "
                "use layout='flat'")
        if len(configs) > 1 or force_run_axis:
            raise ValueError(
                "delta parameterization is single-run: the sweep lattice "
                "shares one state buffer per run and does not thread the "
                "per-run base rows")
        if n_shards > 1:
            raise ValueError(
                "delta parameterization lowers on the single-device flat "
                "engine (the sharded halo exchanges dense row blocks); "
                "use n_shards=1 or delta='none'")
    if n_model_shards < 1:
        raise ValueError(f"n_model_shards must be >= 1, got {n_model_shards}")
    if n_model_shards > 1:
        if layout == "tree":
            raise model_axis_conflict(
                "layout 'tree' (the pytree engine has no flat buffer to "
                "column-shard)")
        if len(configs) > 1 or force_run_axis:
            raise model_axis_conflict(
                "sweep lattices (--sweep-runs) until the composition lands")
        if delta != "none":
            raise model_axis_conflict("delta parameterization (--delta)")
        c0 = configs[0]
        if (getattr(c0, "gossip_compress", "none").startswith("topk")
                and c0.gossip_impl != "none"):
            raise model_axis_conflict(
                "topk gossip compression (the payload indices address the "
                "full D axis)")
    if fuse_update_mix:
        if layout == "tree":
            raise ValueError(
                "fuse_update_mix needs the flat (n, D) buffer layout — the "
                "update+mix kernels tile one contiguous buffer; use "
                "layout='flat'")
        if n_shards > 1:
            raise ValueError(
                "fuse_update_mix is single-device: the sharded engine "
                "overlaps its halo with interior compute instead "
                "(core/sharded.py); use n_shards=1")
        if n_model_shards > 1:
            raise model_axis_conflict("fuse_update_mix (--fuse-update-mix)")
    spec = EngineSpec(configs=configs, layout=layout, n_shards=n_shards,
                      axis_name=axis_name, t_steps=t_steps,
                      force_run_axis=force_run_axis, delta=delta,
                      n_model_shards=n_model_shards, model_axis=model_axis,
                      fuse_update_mix=fuse_update_mix)
    if spec.has_run_axis or t_steps is not None:
        spec.plan()  # full lattice validation (raises on bad combinations)
    return spec


# ---------------------------------------------------------------------------
# Lowering dispatch: EngineSpec -> executor
# ---------------------------------------------------------------------------


def _dispatch(espec: EngineSpec, flat_spec, mesh):
    if espec.layout == "tree":
        return "tree"
    if flat_spec is None:
        raise ValueError("flat layouts need a FlatSpec (flat.make_flat_spec)")
    if espec.is_sharded and mesh is None:
        raise ValueError("n_shards > 1 needs a device mesh (mesh=...)")
    if espec.is_model_sharded and mesh is None:
        raise ValueError("n_model_shards > 1 needs a 2-D device mesh "
                         "(launch.mesh.make_fed_mesh)")
    if espec.is_model_sharded:
        return "sharded"
    if espec.has_run_axis:
        return "sharded_sweep" if mesh is not None else "sweep"
    return "sharded" if mesh is not None else "flat"


def make_engine_round(espec: EngineSpec, grad_fn: GradFn, lr_fn: LrFn, *,
                      flat_spec=None, mesh=None, gossip_fn=None,
                      optimizer=None, metrics_fn=None,
                      block_d: int | None = None, donate: bool = True,
                      jit: bool = True, unroll: int = 1,
                      per_step_keys: bool = False, delta_base=None):
    """Lower an EngineSpec to its fused-round executor.

    Dispatch: layout 'tree' → the tree engine; a run axis → the sweep
    engine; a mesh → the sharded engine; both → the sharded-sweep
    composition.  The per-engine ``make_*_feddec_round`` constructors are
    shims over this function.  ``delta_base`` is the shared (D,) base row
    of a ``delta != 'none'`` spec (defaults to zeros — every agent row is
    then its own delta).
    """
    kind = _dispatch(espec, flat_spec, mesh)
    if kind in ("sweep", "sharded_sweep") and gossip_fn is not None:
        raise ValueError("gossip_fn overrides are single-run only")
    if kind in ("tree", "flat", "sharded") and per_step_keys:
        raise ValueError("per_step_keys needs a run axis (sweep lowering)")
    if kind == "sharded" and metrics_fn is not None:
        raise ValueError("metrics_fn is not supported by the single-run "
                         "sharded lowering")
    if delta_base is not None and espec.delta == "none":
        raise ValueError("delta_base was passed but the spec has "
                         "delta='none'")
    if espec.fuse_update_mix and kind not in ("flat", "sweep"):
        raise ValueError(
            "fuse_update_mix lowers on the flat / sweep engines only; the "
            f"'{kind}' lowering was selected (drop the mesh or the flag)")

    if kind == "tree":
        from repro.core import feddec
        return feddec._lower_tree_round(
            espec.cfg, grad_fn, lr_fn, gossip_fn=gossip_fn,
            optimizer=optimizer, metrics_fn=metrics_fn, donate=donate,
            jit=jit, unroll=unroll)
    if kind == "flat":
        from repro.core import flat as flat_lib
        return flat_lib._lower_flat_round(
            espec.cfg, flat_spec, grad_fn, lr_fn, gossip_fn=gossip_fn,
            optimizer=optimizer, metrics_fn=metrics_fn, donate=donate,
            jit=jit, unroll=unroll, delta_base=delta_base,
            fuse_update_mix=espec.fuse_update_mix)
    if kind == "sweep":
        from repro.core import sweep as sweep_lib
        return sweep_lib._lower_sweep_round(
            espec.plan(), flat_spec, grad_fn, lr_fn, optimizer=optimizer,
            metrics_fn=metrics_fn, block_d=block_d, donate=donate, jit=jit,
            unroll=unroll, per_step_keys=per_step_keys,
            fuse_update_mix=espec.fuse_update_mix)
    if kind == "sharded":
        from repro.core import sharded as sharded_lib
        return sharded_lib._lower_sharded_round(
            espec.cfg, flat_spec, grad_fn, lr_fn, mesh,
            axis_name=espec.axis_name, optimizer=optimizer, block_d=block_d,
            donate=donate, jit=jit, unroll=unroll,
            model_axis=(espec.model_axis if espec.is_model_sharded
                        else None))
    return make_sharded_sweep_round(
        espec.plan(), flat_spec, grad_fn, lr_fn, mesh,
        axis_name=espec.axis_name, optimizer=optimizer,
        metrics_fn=metrics_fn, block_d=block_d, donate=donate, jit=jit,
        unroll=unroll, per_step_keys=per_step_keys)


def make_engine_step(espec: EngineSpec, grad_fn: GradFn, lr_fn: LrFn, *,
                     flat_spec=None, mesh=None, gossip_fn=None,
                     optimizer=None, block_d: int | None = None,
                     donate: bool = True, jit: bool = True,
                     delta_base=None):
    """Lower an EngineSpec to its one-iteration executor (same dispatch as
    :func:`make_engine_round`)."""
    kind = _dispatch(espec, flat_spec, mesh)
    if kind in ("sweep", "sharded_sweep") and gossip_fn is not None:
        raise ValueError("gossip_fn overrides are single-run only")
    if delta_base is not None and espec.delta == "none":
        raise ValueError("delta_base was passed but the spec has "
                         "delta='none'")
    if espec.fuse_update_mix and kind not in ("flat", "sweep"):
        raise ValueError(
            "fuse_update_mix lowers on the flat / sweep engines only; the "
            f"'{kind}' lowering was selected (drop the mesh or the flag)")

    if kind == "tree":
        from repro.core import feddec
        return feddec._lower_tree_step(
            espec.cfg, grad_fn, lr_fn, gossip_fn=gossip_fn,
            optimizer=optimizer, donate=donate, jit=jit)
    if kind == "flat":
        from repro.core import flat as flat_lib
        return flat_lib._lower_flat_step(
            espec.cfg, flat_spec, grad_fn, lr_fn, gossip_fn=gossip_fn,
            optimizer=optimizer, donate=donate, jit=jit,
            delta_base=delta_base,
            fuse_update_mix=espec.fuse_update_mix)
    if kind == "sweep":
        from repro.core import sweep as sweep_lib
        return sweep_lib._lower_sweep_step(
            espec.plan(), flat_spec, grad_fn, lr_fn, optimizer=optimizer,
            block_d=block_d, donate=donate, jit=jit,
            fuse_update_mix=espec.fuse_update_mix)
    if kind == "sharded":
        from repro.core import sharded as sharded_lib
        return sharded_lib._lower_sharded_step(
            espec.cfg, flat_spec, grad_fn, lr_fn, mesh,
            axis_name=espec.axis_name, optimizer=optimizer, block_d=block_d,
            donate=donate, jit=jit,
            model_axis=(espec.model_axis if espec.is_model_sharded
                        else None))
    return make_sharded_sweep_step(
        espec.plan(), flat_spec, grad_fn, lr_fn, mesh,
        axis_name=espec.axis_name, optimizer=optimizer, block_d=block_d,
        donate=donate, jit=jit)


# ---------------------------------------------------------------------------
# The sharded-sweep composition: R runs × s shards in one program
# ---------------------------------------------------------------------------


def _union_support_graph(plan) -> topo.Graph:
    """OR of every non-FedAvg run's mixing support.

    The lattice shares ONE halo schedule: per-run W^t entries are zero off
    their own graph's support, so exchanging blocks over the union quotient
    is exact for every run (a run without a given cut edge multiplies the
    received block by zeros).
    """
    n = plan.n_agents
    adj = np.zeros((n, n), dtype=bool)
    for c, nm in zip(plan.configs, plan.none_mask):
        if not nm:
            adj |= np.asarray(c.mixing.graph.adjacency)
    return topo.Graph(adj, name="sweep-union")


def _sweep_halo_setup(plan, n_shards: int):
    """ppermute schedule over the union quotient (cf. sharded._halo_setup)."""
    from repro.core import sharded as sharded_lib
    q = sharded_lib.quotient_graph(_union_support_graph(plan), n_shards)
    schedule = topo.permutation_schedule(q)
    perms = jnp.asarray(
        np.stack(schedule) if schedule
        else np.zeros((0, n_shards), np.int64), jnp.int32)
    pairs = [tuple((int(p[d]), d) for d in range(n_shards) if p[d] != d)
             for p in schedule]
    return perms, pairs


def _sweep_blk_mix(impl: str, block_d: int | None):
    """(R, n_local, n_local) @ (R, n_local, D) sub-block contraction."""
    if impl == "pallas":
        from repro.kernels import ops as kernel_ops

        def blk_mix(wb, xb):
            if block_d is None:
                return kernel_ops.gossip_mix_batched(wb, xb)
            return kernel_ops.gossip_mix_batched(wb, xb, block_d=block_d)
        return blk_mix

    def blk_mix(wb, xb):
        return jnp.einsum("rij,rjd->rid", wb.astype(xb.dtype), xb,
                          precision=_HIGHEST)
    return blk_mix


def _sweep_halo_wblk(w, lo, src, me, r_runs: int, n_local: int):
    """Round-r weight sub-blocks W[:, rows, src-block]; idle shards this
    round (perm[me] == me) received zeros and must not re-add their own."""
    wblk = jax.lax.dynamic_slice(w, (0, lo, src * n_local),
                                 (r_runs, n_local, n_local))
    return jnp.where(src == me, 0.0, 1.0).astype(wblk.dtype) * wblk


def _make_sweep_shard_mixer(plan, axis_name, n_shards: int,
                            block_d: int | None = None):
    """Per-shard whole-lattice mix(w (R,n,n), x_blk (R,n_local,D), me)."""
    impl = plan.gossip_impl
    r, n = plan.r_runs, plan.n_agents
    n_local = n // n_shards

    if impl == "none":
        return lambda w, x_blk, me: x_blk

    if impl == "dense":
        def mix(w, x_blk, me):
            cols = jax.lax.dynamic_slice(w, (0, 0, me * n_local),
                                         (r, n, n_local))
            partial = jnp.einsum("rij,rjd->rid", cols.astype(x_blk.dtype),
                                 x_blk, precision=_HIGHEST)
            if n_shards == 1:
                return partial
            return jax.lax.psum_scatter(partial, axis_name,
                                        scatter_dimension=1, tiled=True)
        return mix

    if impl in ("sparse", "pallas"):
        perms, pairs = _sweep_halo_setup(plan, n_shards)
        blk_mix = _sweep_blk_mix(impl, block_d)

        def mix(w, x_blk, me):
            lo = me * n_local
            own = jax.lax.dynamic_slice(w, (0, lo, lo), (r, n_local, n_local))
            y = blk_mix(own, x_blk)
            for rr, pr in enumerate(pairs):
                recv = jax.lax.ppermute(x_blk, axis_name, perm=pr)
                wblk = _sweep_halo_wblk(w, lo, perms[rr, me], me, r, n_local)
                y = y + blk_mix(wblk, recv)
            return y
        return mix

    raise unknown_gossip_impl(impl)


def _make_compressed_sweep_shard_mixer(plan, axis_name, n_shards: int,
                                       compressor,
                                       block_d: int | None = None):
    """Compressed per-shard lattice mixer: y = W s + diag(W)(p − s) per run;
    the sparse/pallas halo ppermutes the *encoded* (R, n_local, ...) payload
    leaves (cf. sharded._make_compressed_shard_mixer)."""
    impl = plan.gossip_impl
    r, n = plan.r_runs, plan.n_agents
    n_local = n // n_shards

    def diag_blk(w, me):  # (R, n_local)
        return jax.lax.dynamic_slice(
            jnp.diagonal(w, axis1=1, axis2=2), (0, me * n_local),
            (r, n_local))

    if impl == "dense":
        def mix(w, p_blk, s_blk, payload, me):
            cols = jax.lax.dynamic_slice(w, (0, 0, me * n_local),
                                         (r, n, n_local))
            partial = jnp.einsum("rij,rjd->rid", cols.astype(s_blk.dtype),
                                 s_blk, precision=_HIGHEST)
            y = partial if n_shards == 1 else jax.lax.psum_scatter(
                partial, axis_name, scatter_dimension=1, tiled=True)
            dg = diag_blk(w, me).astype(p_blk.dtype)[:, :, None]
            return y + dg * (p_blk - s_blk)
        return mix

    if impl in ("sparse", "pallas"):
        perms, pairs = _sweep_halo_setup(plan, n_shards)
        blk_mix = _sweep_blk_mix(impl, block_d)

        def mix(w, p_blk, s_blk, payload, me):
            lo = me * n_local
            own = jax.lax.dynamic_slice(w, (0, lo, lo), (r, n_local, n_local))
            dg = diag_blk(w, me).astype(p_blk.dtype)[:, :, None]
            y = blk_mix(own, s_blk) + dg * (p_blk - s_blk)
            for rr, pr in enumerate(pairs):
                # the halo moves the *encoded* payload, leaf by leaf
                recv = jax.tree.map(
                    lambda a: jax.lax.ppermute(a, axis_name, perm=pr),
                    payload)
                s_recv = jax.vmap(
                    lambda pl: compressor.decode(pl, p_blk.dtype,
                                                 p_blk.shape[-1]))(recv)
                wblk = _sweep_halo_wblk(w, lo, perms[rr, me], me, r, n_local)
                y = y + blk_mix(wblk, s_recv)
            return y
        return mix

    raise unknown_gossip_impl(impl)


def _encode_sweep_shard_block(compressor, key_c, n_agents: int, n_local: int,
                              me, x_blk, res_blk):
    """Per-shard batched EF encode → (payload, s_blk, new_res).

    Per-run per-agent codec keys are derived replicated (split(key_c[r], n))
    and row-sliced, so every run's rounding noise matches the single-run
    flat engine — and the sweep engine — bit for bit.
    """
    from repro.core import sharded as sharded_lib
    u = x_blk + res_blk
    if compressor.needs_key:
        keys = jax.vmap(
            lambda k: sharded_lib._slice_agent_keys(
                jax.random.split(k, n_agents), me * n_local, n_local))(key_c)
        payload = jax.vmap(compressor.encode)(keys, u)
    else:
        payload = jax.vmap(lambda uu: compressor.encode(None, uu))(u)
    s_blk = jax.vmap(
        lambda pl: compressor.decode(pl, u.dtype, u.shape[-1]))(payload)
    return payload, s_blk, u - s_blk


def _sweep_shard_ops(plan, spec, grad_fn: GradFn, lr_fn: LrFn, axis_name,
                     n_shards: int, optimizer, block_d) -> EngineOps:
    """EngineOps of the sharded-sweep composition.

    Carry: ``(flat_blk (R, n_local, D), res_blk, opt_blk, t (R,))`` — the
    sweep engine's per-run layout restricted to this shard's agent block.
    Replicated compute (keys, η, W sampling, server draws) is identical to
    the sweep engine; collectives mirror the sharded engine with a leading
    run axis.
    """
    from repro.core import sweep as sweep_lib
    r, n = plan.r_runs, plan.n_agents
    n_local = n // n_shards
    sample_w = sweep_lib.make_sweep_w_sampler(plan)
    h_arr = jnp.asarray(plan.h)
    t_max = None if plan.t_steps is None else jnp.asarray(plan.t_steps)
    compressor = compress_lib.parse_compress(plan.gossip_compress) \
        if plan.gossip_impl != "none" else None
    none3 = jnp.asarray(plan.none_mask)[:, None, None] \
        if compressor is not None and plan.none_mask.any() else None

    if compressor is None:
        mixer = _make_sweep_shard_mixer(plan, axis_name, n_shards,
                                        block_d=block_d)
    else:
        cmixer = _make_compressed_sweep_shard_mixer(
            plan, axis_name, n_shards, compressor, block_d=block_d)

    def derive_keys(keys, t):
        k3 = jax.vmap(lambda k, tt: jax.random.split(
            jax.random.fold_in(k, tt), 3))(keys, t)
        return k3[:, 0], k3[:, 1], k3[:, 2]

    def local_update(state, batch_blk, key_grad, eta):
        flat_blk = state[0]
        me = jax.lax.axis_index(axis_name)
        from repro.core import sharded as sharded_lib
        params = spec.unflatten(flat_blk.reshape(r * n_local, spec.d))
        # run r's agent keys: the full replicated split(key_grad[r], n),
        # row-sliced to this shard's block — bit-identical to both the
        # sweep and the single-run engines
        agent_keys = jax.vmap(
            lambda k: sharded_lib._slice_agent_keys(
                jax.random.split(k, n), me * n_local, n_local))(key_grad)
        batch_rn = jax.tree.map(
            lambda b: b.reshape((r * n_local,) + b.shape[2:]), batch_blk)
        losses, grads = jax.vmap(grad_fn)(params,
                                          batch_rn,
                                          agent_keys.reshape(r * n_local))
        g3 = spec.flatten(grads).reshape(r, n_local, spec.d)
        losses = losses.reshape(r, n_local)
        if optimizer is None:  # plain SGD: one pass over (R, n_local, D)
            x_half = flat_blk - eta[:, None, None].astype(spec.dtype) * g3
            new_opt = state[2]
        else:
            x_half, new_opt = jax.vmap(optimizer.update)(
                flat_blk, g3, state[2], eta)
        return losses, x_half, new_opt

    def gossip(w, x_half):
        return mixer(w, x_half, jax.lax.axis_index(axis_name))

    def ef_gossip(w, x_half, res_blk, key_c):
        me = jax.lax.axis_index(axis_name)
        payload, s_blk, new_res = _encode_sweep_shard_block(
            compressor, key_c, n, n_local, me, x_half, res_blk)
        x_next = cmixer(w, x_half, s_blk, payload, me)
        if none3 is not None:
            # FedAvg lattice members exchange nothing: bypass the codec so
            # their trajectories stay bit-identical to the uncompressed path
            x_next = jnp.where(none3, x_half, x_next)
            new_res = jnp.where(none3, res_blk, new_res)
        return x_next, new_res

    def server(key_server, x_next, t):
        if not plan.server_enabled:
            return x_next
        me = jax.lax.axis_index(axis_name)
        counts = jax.vmap(
            lambda k: server_lib.sample_participants(k, n, plan.k))(
            key_server)
        wts = server_lib.participant_weights(counts, plan.k)        # (R, n)
        w_blk = jax.lax.dynamic_slice(wts, (0, me * n_local), (r, n_local))
        z = jnp.einsum("rj,rjd->rd", w_blk.astype(x_next.dtype), x_next,
                       precision=_HIGHEST)
        if n_shards > 1:
            z = jax.lax.psum(z, axis_name)
        z_all = jnp.broadcast_to(z[:, None], x_next.shape)
        is_round = ((t + 1) % h_arr == 0)[:, None, None]
        return jnp.where(is_round, z_all, x_next)

    def finish(state, z_next, new_opt, new_res, t, losses, eta):
        loss = jnp.sum(losses, axis=1)
        if n_shards > 1:
            loss = jax.lax.psum(loss, axis_name)
        metrics = {"loss": loss / n, "eta": eta}
        new_carry = (z_next, new_res, new_opt, t + 1)
        if t_max is not None:
            # heterogeneous budgets: finished runs freeze (state preserved
            # bitwise — every carried leaf has a leading run axis)
            active = t <= t_max

            def keep(new, old):
                m = active.reshape((r,) + (1,) * (new.ndim - 1))
                return jnp.where(m, new, old)
            new_carry = jax.tree.map(keep, new_carry, state)
            metrics["active"] = active
        return new_carry, metrics

    return EngineOps(
        get_step=lambda state: state[3],
        derive_keys=derive_keys,
        eta_fn=lambda t: jnp.broadcast_to(jnp.asarray(lr_fn(t)), (r,)),
        sample_w=sample_w,
        local_update=local_update,
        gossip=(lambda w, x: x) if compressor is not None else gossip,
        get_residual=lambda state: state[1],
        server=server,
        finish=finish,
        fold_codec=None if compressor is None else (
            lambda key_w: jax.vmap(
                lambda k: jax.random.fold_in(k, 1))(key_w)),
        ef_gossip=None if compressor is None else ef_gossip)


def _sweep_opt_specs(optimizer, spec, r_runs: int, n_agents: int, axis_name):
    if optimizer is None:
        return ()
    struct = jax.eval_shape(
        lambda x: jax.vmap(optimizer.init)(x),
        jax.ShapeDtypeStruct((r_runs, n_agents, spec.d), spec.dtype))
    return jax.tree.map(
        lambda s: P(None, axis_name) if s.ndim == 3 else P(), struct)


def _sweep_leaf_spec(leaf, axis_name) -> P:
    """THE sharding rule for sweep-state leaves on an agent mesh: (R, n, D)
    buffers shard their agent dim, (R,) counters replicate."""
    return P(None, axis_name) if getattr(leaf, "ndim", 0) == 3 else P()


def sweep_state_specs(plan, spec, optimizer=None,
                      axis_name="agents"):
    """SweepFedState pytree of PartitionSpecs for the sharded-sweep engine."""
    from repro.core.sweep import SweepFedState
    compress = plan.gossip_compress if plan.gossip_impl != "none" else "none"
    return SweepFedState(
        flat=P(None, axis_name), step=P(),
        opt_state=_sweep_opt_specs(optimizer, spec, plan.r_runs,
                                   plan.n_agents, axis_name),
        residual=() if compress == "none" else P(None, axis_name))


def shard_sweep_state(state, mesh: jax.sharding.Mesh, axis_name="agents"):
    """Place a SweepFedState on the mesh, agent dim block-sharded per run."""
    specs = jax.tree.map(lambda l: _sweep_leaf_spec(l, axis_name), state)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(state, shardings)


def _sharded_sweep_setup(plan, spec, grad_fn, lr_fn, mesh, axis_name,
                         optimizer, block_d):
    from repro.core import sharded as sharded_lib
    ax = sharded_lib._resolve_axis(mesh, axis_name)
    n_shards = sharded_lib.agent_axis_size(mesh, ax)
    if plan.n_agents % n_shards:
        raise ValueError(
            f"n_agents={plan.n_agents} must be divisible by the agent axis "
            f"size {n_shards} (block-sharded rows)")
    ops = _sweep_shard_ops(plan, spec, grad_fn, lr_fn, ax, n_shards,
                           optimizer, block_d)
    opt_specs = _sweep_opt_specs(optimizer, spec, plan.r_runs,
                                 plan.n_agents, ax)
    res_specs = () if plan.gossip_compress == "none" \
        or plan.gossip_impl == "none" else P(None, ax)
    return ax, n_shards, ops, opt_specs, res_specs


def _sweep_metric_specs(plan, stacked: bool):
    base = P(None) if stacked else P()
    specs = {"loss": base, "eta": base}
    if plan.t_steps is not None:
        specs["active"] = base
    return specs


def make_sharded_sweep_step(plan, spec, grad_fn: GradFn, lr_fn: LrFn,
                            mesh: jax.sharding.Mesh, *,
                            axis_name="agents", optimizer=None,
                            block_d: int | None = None, donate: bool = True,
                            jit: bool = True):
    """One-iteration sharded-sweep executor: step(state, batch, keys)
    advances all R runs by one Algorithm-1 step, agents sharded over the
    mesh.  ``batch`` leaves are (R, n, ...) consumed ``P(None, axis)``;
    ``keys`` is a (R,) key array (run r's key = the single-run engine's).
    """
    from repro.core import sharded  # noqa: F401  (validates availability)
    ax, n_shards, ops, opt_specs, res_specs = _sharded_sweep_setup(
        plan, spec, grad_fn, lr_fn, mesh, axis_name, optimizer, block_d)
    body = build_step_body(ops)
    metric_specs = _sweep_metric_specs(plan, stacked=False)

    def per_shard(flat_blk, res_blk, opt_blk, t, batch_blk, keys):
        (z, res, opt, t1), metrics = body((flat_blk, res_blk, opt_blk, t),
                                          batch_blk, keys)
        return z, res, opt, t1, metrics

    from repro.core.sharded import _shard_map
    smapped = _shard_map(
        per_shard, mesh,
        in_specs=(P(None, ax), res_specs, opt_specs, P(), P(None, ax), P()),
        out_specs=(P(None, ax), res_specs, opt_specs, P(), metric_specs))

    def step(state, batch, keys):
        from repro.core.sweep import SweepFedState
        flat, res, opt, t, metrics = smapped(state.flat, state.residual,
                                             state.opt_state, state.step,
                                             batch, keys)
        return SweepFedState(flat=flat, step=t, opt_state=opt,
                             residual=res), metrics

    return finalize_executor(step, donate=donate, jit=jit)


def make_sharded_sweep_round(plan, spec, grad_fn: GradFn, lr_fn: LrFn,
                             mesh: jax.sharding.Mesh, *,
                             axis_name="agents", optimizer=None,
                             metrics_fn=None, block_d: int | None = None,
                             donate: bool = True, jit: bool = True,
                             unroll: int = 1, per_step_keys: bool = False):
    """The fused sharded-sweep executor: T steps × R runs × s shards, one
    program.

    Contract: the sweep engine's (``batches`` leaves (T, R, n, ...), metrics
    stacked to (T, R), ``keys`` (R,) or (T, R) with ``per_step_keys``) with
    the agent dim consumed block-sharded over the mesh axis — the whole
    ``lax.scan`` runs inside one ``shard_map``, so the per-step collectives
    (psum_scatter / union-quotient ppermute halo / server psum) are the only
    cross-device traffic of the entire lattice.  Every run slice matches the
    single-run flat engine to ≤ 1e-5.  ``metrics_fn`` receives the post-step
    per-shard carry as a SweepFedState view of this shard's block.
    """
    ax, n_shards, ops, opt_specs, res_specs = _sharded_sweep_setup(
        plan, spec, grad_fn, lr_fn, mesh, axis_name, optimizer, block_d)
    body = build_step_body(ops)
    metric_specs = _sweep_metric_specs(plan, stacked=True)
    if metrics_fn is not None:
        from repro.core.sweep import SweepFedState

        def merged_step(carry, batch, keys):
            new_carry, metrics = body(carry, batch, keys)
            view = SweepFedState(flat=new_carry[0], step=new_carry[3],
                                 opt_state=new_carry[2],
                                 residual=new_carry[1])
            return new_carry, {**metrics, **metrics_fn(view)}
    else:
        merged_step = body

    def per_shard_round(flat_blk, res_blk, opt_blk, t0, batches_blk, keys):
        def scan_body(carry, xs):
            batch, kk = xs if per_step_keys else (xs, keys)
            return merged_step(carry, batch, kk)

        xs = (batches_blk, keys) if per_step_keys else batches_blk
        (x, res, opt, t), metrics = jax.lax.scan(
            scan_body, (flat_blk, res_blk, opt_blk, t0), xs, unroll=unroll)
        return x, res, opt, t, metrics

    from repro.core.sharded import _shard_map
    smapped = _shard_map(
        per_shard_round, mesh,
        in_specs=(P(None, ax), res_specs, opt_specs, P(),
                  P(None, None, ax), P()),
        out_specs=(P(None, ax), res_specs, opt_specs, P(), metric_specs))

    def round_fn(state, batches, keys):
        from repro.core.sweep import SweepFedState
        flat, res, opt, t, metrics = smapped(state.flat, state.residual,
                                             state.opt_state, state.step,
                                             batches, keys)
        return SweepFedState(flat=flat, step=t, opt_state=opt,
                             residual=res), metrics

    return finalize_executor(round_fn, donate=donate, jit=jit)
