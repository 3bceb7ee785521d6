"""Step builders binding (architecture × shape × mesh) to executable fns.

Three step kinds, matching the assigned shapes:

  * train  (train_4k)    — the FedDec step (Alg. 1) over stacked per-agent
    params: vmapped fwd/bwd, local SGD, gossip, periodic server round.
  * prefill (prefill_32k) — single forward over the full sequence
    (inference prefill; unstacked serving params).
  * decode (decode_32k, long_500k) — one-token serve step against KV/state
    caches of length seq_len.

Everything here returns *unjitted* python callables plus the matching
ShapeDtypeStruct/PartitionSpec trees; launch/dryrun.py owns jit/lower/compile
and launch/train.py owns the real training loop.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from repro.configs.base import ArchConfig, FedConfig
from repro.configs.shapes import ShapeConfig
from repro.core import (engine as engine_lib, feddec, flat as flat_lib,
                        sharded as sharded_lib, sweep as sweep_lib,
                        topology as topo)
from repro.core.mixing import MixingDistribution
from repro.launch import specs as specs_lib
from repro.models import build_model

__all__ = ["build_fed_setup", "sweep_lattice_configs", "Lowerable",
           "build_train_lowerable", "build_prefill_lowerable",
           "build_decode_lowerable", "build_lowerable"]


def adapt_for_mesh(cfg: ArchConfig, axes: shd.MeshAxes) -> ArchConfig:
    """Mesh-dependent config tweaks applied at lowering time only.

    When the head count doesn't divide the TP axis, QKV weights are
    contracting-dim-sharded and must gather-on-use (the smoke tests run the
    raw config on one device, where the constraint would be a no-op anyway
    but the flag stays off to keep their HLO clean).
    """
    if (cfg.attention_kind == "gqa"
            and cfg.num_heads % axes.model_size != 0):
        cfg = dataclasses.replace(cfg, attn_weight_gather=True)
    cfg = dataclasses.replace(cfg, tp_axis_name=axes.model_axis)
    return cfg


def build_fed_setup(cfg: ArchConfig, axes: shd.MeshAxes,
                    fed: FedConfig | None = None):
    """(FedDecConfig, n_agents) for this arch on this mesh."""
    n = shd.n_agents_for(cfg, axes)
    fed = fed or FedConfig()
    if fed.graph.startswith("ring"):
        k = int(fed.graph[4:] or 2)
        graph = topo.ring_graph(n, k=min(k, (n - 1) // 2 or 1))
    elif fed.graph == "full":
        graph = topo.fully_connected_graph(n)
    elif fed.graph.startswith("geo"):
        graph = topo.geographic_graph(n, float(fed.graph[3:]), seed=0)
    elif fed.graph.startswith("er"):
        graph = topo.erdos_renyi_graph(n, float(fed.graph[2:]), seed=0)
    else:
        raise ValueError(f"unknown graph {fed.graph!r}")
    mixing = MixingDistribution(graph, p_fail=fed.p_fail,
                                scheme="metropolis")
    # 'permute' is a gossip_fn built on the mesh (make_permute_gossip), not
    # a FedDecConfig impl — the config falls back to dense there; any other
    # unknown impl is left for FedDecConfig's validation to reject
    impl = "dense" if fed.gossip_impl == "permute" else fed.gossip_impl
    fcfg = feddec.FedDecConfig(mixing=mixing, h=fed.h,
                               k=min(fed.k, n), gossip_impl=impl,
                               gossip_compress=fed.gossip_compress,
                               delta=fed.delta)
    return fcfg, n


def sweep_lattice_configs(fcfg: feddec.FedDecConfig, fed: FedConfig | None,
                          sweep_runs: int,
                          sweep_axis: str = "seed") -> list:
    """Per-run FedDecConfigs for a --sweep-runs lattice.

    ``seed``     — R replicas of the base config (the runs differ only in
                   their per-run PRNG keys, supplied by the driver);
    ``h``        — doubling server-period lattice H·{1, 2, 4, …} (the
                   paper's Fig. 4 axis);
    ``topology`` — R independent draws of the base graph family (geo/er
                   re-drawn with seed = run index; deterministic families
                   have nothing to sweep and are rejected).
    """
    fed = fed or FedConfig()
    if sweep_axis == "seed":
        return [fcfg] * sweep_runs
    if sweep_axis == "h":
        return [dataclasses.replace(fcfg, h=fcfg.h * (1 << r))
                for r in range(sweep_runs)]
    if sweep_axis == "topology":
        n = fcfg.n_agents
        if fed.graph.startswith("geo"):
            graphs = [topo.geographic_graph(n, float(fed.graph[3:]), seed=r)
                      for r in range(sweep_runs)]
        elif fed.graph.startswith("er"):
            graphs = [topo.erdos_renyi_graph(n, float(fed.graph[2:]), seed=r)
                      for r in range(sweep_runs)]
        else:
            raise ValueError(
                f"--sweep-axis topology needs a random graph family "
                f"(geoR/erP), got {fed.graph!r}")
        return [dataclasses.replace(
            fcfg, mixing=MixingDistribution(g, p_fail=fed.p_fail,
                                            scheme="metropolis"))
            for g in graphs]
    raise ValueError(f"unknown sweep_axis {sweep_axis!r}; choose "
                     f"seed|h|topology")


@dataclasses.dataclass(frozen=True)
class Lowerable:
    """A step function plus everything needed to lower it on a mesh."""

    fn: Callable                  # positional-args step
    args_struct: tuple            # ShapeDtypeStructs per arg
    in_specs: tuple               # PartitionSpecs per arg
    out_specs: Any = None         # PartitionSpecs for outputs (None ⇒ XLA)
    donate_argnums: tuple = ()
    name: str = "step"

    def lower(self, mesh: jax.sharding.Mesh):
        def shard(tree):
            return jax.tree.map(lambda s: jax.NamedSharding(mesh, s), tree,
                                is_leaf=lambda x: isinstance(x, P))
        kw = {}
        if self.out_specs is not None:
            kw["out_shardings"] = shard(self.out_specs)
        jitted = jax.jit(self.fn, in_shardings=shard(self.in_specs),
                         donate_argnums=self.donate_argnums, **kw)
        with jax.set_mesh(mesh):
            return jitted.lower(*self.args_struct)


def _key_struct():
    return jax.eval_shape(lambda: jax.random.key(0))


def _microbatch_grad(base_grad: Callable, num_micro: int) -> Callable:
    """Gradient accumulation: split the per-agent batch into ``num_micro``
    sequential microbatches (lax.scan), averaging loss and grads.

    This bounds live activations to one microbatch — the standard memory
    lever when per-device HBM can't hold a full step's remat carries.
    """
    if num_micro <= 1:
        return base_grad

    def split(path, x):
        names = [getattr(p, "key", str(p)) for p in path]
        bd = 1 if "mrope_positions" in names else 0  # per-agent (3, B, S)
        assert x.shape[bd] % num_micro == 0, (names, x.shape, num_micro)
        shape = (x.shape[:bd] + (num_micro, x.shape[bd] // num_micro)
                 + x.shape[bd + 1:])
        return jnp.moveaxis(x.reshape(shape), bd, 0)

    def grad_fn(params, batch, key):
        micro = jax.tree_util.tree_map_with_path(split, batch)

        def body(carry, mb):
            loss_acc, grad_acc = carry
            loss, grads = base_grad(params, mb, key)
            grad_acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype),
                                    grad_acc, grads)
            return (loss_acc + loss, grad_acc), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (loss, grads), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro)
        inv = 1.0 / num_micro
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    return grad_fn


def _default_microbatches(cfg: ArchConfig, per_agent_batch: int,
                          axes: shd.MeshAxes) -> int:
    """Pick num_micro so ~one sequence per device is live per microbatch."""
    if cfg.fed_agent_layout == "sharded":
        per_device = per_agent_batch            # batch replicated over model
    else:
        per_device = max(1, per_agent_batch // axes.data_size)
    m = min(per_agent_batch, per_device)
    while per_agent_batch % m:
        m -= 1
    return max(1, m)


def build_train_lowerable(cfg: ArchConfig, shape: ShapeConfig,
                          axes: shd.MeshAxes, *,
                          fed: FedConfig | None = None,
                          lr: float = 1e-2,
                          microbatches: int | None = None,
                          mesh: jax.sharding.Mesh | None = None,
                          fused_steps: int | None = None,
                          state_layout: str = "tree",
                          mesh_model: int | None = None,
                          sweep_runs: int | None = None,
                          sweep_axis: str = "seed",
                          fuse_update_mix: bool = False) -> Lowerable:
    """The FedDec training step at production shape.

    ``fed.gossip_impl='permute'`` selects the neighbour-only ppermute gossip
    schedule (needs ``mesh``; sharded agent layout only) — the optimized
    path of §Perf iteration A1.  ``'pallas'``/``'sparse'`` select the
    streaming-kernel / CSR gather paths (repro.core.feddec.resolve_tree_gossip
    on the tree layout, whole-buffer ops on the flat layout).  Default is the
    paper-faithful dense einsum.

    ``fused_steps=H`` lowers the fused round executor instead of the single
    step: batches gain a leading (H,) fused-step dim, all H iterations
    (gossip, server round included) run in one compiled ``lax.scan``, and
    metrics come back stacked ``(H,)``.

    ``state_layout='flat'`` lowers the single-buffer engine
    (repro.core.flat): the carried state is one contiguous (n_agents, D)
    buffer sharded over the agent axes (each agent's row stays whole — the
    flat layout trades inner tensor-parallel sharding for whole-buffer ops,
    so it suits archs whose per-agent replica fits a device slice).

    ``sweep_runs=R`` lowers the batched sweep engine (repro.core.sweep) on
    the flat layout: the carried state is one (R, n_agents, D) lattice
    buffer, batches gain a run axis after the fused-step dim, and the keys
    argument becomes a (R,) per-run key array.  ``sweep_axis`` picks the
    lattice (seed | h | topology, see :func:`sweep_lattice_configs`).
    Requires ``state_layout='flat'`` or ``'sharded'`` and ``fused_steps``.
    With ``state_layout='sharded'`` the composition lowers: the whole
    (R, n_agents, D) lattice runs with the agent dim block-sharded over
    the mesh's data axes — an (R, n_agents/s, D) block per device, the
    full T-step scan inside one shard_map
    (repro.core.engine.make_sharded_sweep_round).

    ``state_layout='sharded'`` lowers the shard_map engine
    (repro.core.sharded) over the same flat buffer: the agent dim is
    block-sharded over the mesh's data axes (needs ``mesh`` and the sharded
    agent layout), gossip is the psum_scatter contraction / ppermute halo
    exchange picked by ``fed.gossip_impl``, and the model runs whole per
    shard (tensor-parallel axis names are cleared — inner TP and the
    shard_map engine are mutually exclusive by design).

    ``mesh_model=M`` (M > 1, sharded layout only) opts into the 2-D
    lowering: the flat buffer's D dim additionally column-shards over the
    mesh's model axis (the full axis width — on the production mesh that
    is all 16 devices of 'model'), gossip and server collectives stay on
    the agent axes, and per-device state scales as n/A x D/M.
    """
    cfg = adapt_for_mesh(cfg, axes)
    if cfg.fed_agent_layout == "replicated":
        # replicated-layout archs shard the per-agent batch over 'data'
        # (sharded-layout agents occupy it instead) — the activation
        # constraints must name it or they force batch replication
        # (§Perf iteration C3)
        cfg = dataclasses.replace(cfg, batch_axis_name="data")
    model = build_model(cfg)
    fcfg, n_agents = build_fed_setup(cfg, axes, fed)
    # the engines carry no residual when W = I exchanges nothing, so the
    # state structs must not either
    compress = fcfg.gossip_compress if fcfg.gossip_impl != "none" else "none"
    per_agent = shape.global_batch // n_agents
    if microbatches is None:
        microbatches = _default_microbatches(cfg, per_agent, axes)
    grad_fn = _microbatch_grad(model.grad_fn(), microbatches)

    params_struct = jax.eval_shape(model.init, jax.random.key(0))
    state_struct = jax.eval_shape(
        lambda p: feddec.init_state(p, n_agents, compress=compress),
        params_struct)
    batch_struct = specs_lib.train_batch_specs(cfg, shape, n_agents)

    param_specs = shd.param_pspecs(cfg, state_struct.params, axes)

    gossip_fn = None
    if fed is not None and fed.gossip_impl == "permute":
        if mesh is None or cfg.fed_agent_layout != "sharded":
            raise ValueError("permute gossip needs a mesh and the sharded "
                             "agent layout")
        from repro.core import gossip as gossip_lib
        agent_ax = axes.data_axes if len(axes.data_axes) > 1 \
            else axes.data_axes[0]
        exch = jnp.bfloat16 if getattr(fed, "gossip_dtype", "f32") == "bf16" \
            else None
        # the flat layout mixes one 2-D buffer leaf sharded over agents
        # only — the per-leaf param specs don't apply there
        gossip_fn = gossip_lib.make_permute_gossip(
            fcfg.mixing.graph, mesh, agent_ax,
            leaf_specs=None if state_layout == "flat" else param_specs,
            exchange_dtype=exch)

    lr_fn = lambda t: jnp.asarray(lr, jnp.float32)  # noqa: E731
    batch_specs = shd.batch_pspecs(cfg, batch_struct, axes, stacked=True)
    name = f"train:{cfg.name}:{shape.name}"

    if state_layout not in ("tree", "flat", "sharded"):
        raise ValueError(f"state_layout must be 'tree', 'flat' or "
                         f"'sharded', got {state_layout!r}")
    if fuse_update_mix and state_layout != "flat":
        # same compatibility lattice as parse_engine_spec's
        raise ValueError(
            "fuse_update_mix needs the flat (n, D) buffer layout "
            "(state_layout='flat'); the sharded engine overlaps its halo "
            "with interior compute instead (core/sharded.py)")
    if state_layout == "sharded":
        if mesh is None or cfg.fed_agent_layout != "sharded":
            raise ValueError("state_layout='sharded' needs a mesh and the "
                             "sharded agent layout")
        if fed is not None and fed.gossip_impl == "permute":
            raise ValueError("the sharded engine subsumes 'permute': use "
                             "gossip_impl='sparse' (ppermute halo exchange)")
        # mesh_model > 1 opts into the 2-D engine: the flat buffer's D dim
        # column-shards over the mesh's model axis and GSPMD partitions
        # grad_fn over that auto axis from the in/out specs alone.  Inner
        # TP / batch constraint names must ALWAYS clear — explicit
        # with_sharding_constraint inside the partially-manual shard_map
        # region trips XLA's manual-subgroup propagation, and 'data'
        # carries the agents (manual) either way.
        model_ax = (axes.model_axis
                    if mesh_model and mesh_model > 1 and axes.model_size > 1
                    else None)
        cfg = dataclasses.replace(
            cfg, tp_axis_name=None, batch_axis_name=None,
            attn_weight_gather=False,
            # the chunked-prefill scan's stacked ys cannot cross the 2-D
            # engine's partially-auto region (see ArchConfig field docs)
            attn_chunked_prefill=cfg.attn_chunked_prefill
            and model_ax is None)
        model = build_model(cfg)
        grad_fn = _microbatch_grad(model.grad_fn(), microbatches)
        params_struct = jax.eval_shape(model.init, jax.random.key(0))
        spec = flat_lib.make_flat_spec(params_struct)
        state_struct = jax.eval_shape(
            lambda p: flat_lib.init_flat_state(spec, p, n_agents,
                                               compress=compress),
            params_struct)
        agent_ax = axes.data_axes if len(axes.data_axes) > 1 \
            else axes.data_axes[0]
        if model_ax is not None and spec.d % axes.model_size:
            raise ValueError(
                f"flat dim D={spec.d} must be divisible by the model axis "
                f"size {axes.model_size} (column-sharded D/M sub-blocks)")
        state_specs = sharded_lib.flat_state_specs(None, spec, n_agents,
                                                   agent_ax,
                                                   compress=compress,
                                                   model_axis=model_ax)

        def _sharded(maker):
            def make(gossip_fn=None, jit=True, **kw):
                if gossip_fn is not None:
                    raise ValueError("the sharded engine resolves gossip "
                                     "from fed.gossip_impl; gossip_fn "
                                     "overrides are a tree/flat feature")
                if kw.get("optimizer") is not None:
                    # state_struct/state_specs above are built without
                    # optimizer buffers; threading one through here would
                    # lower with inconsistent arg structs
                    raise ValueError("optimizer state is not threaded "
                                     "through the sharded lowerable yet")
                return maker(fcfg, spec, grad_fn, lr_fn, mesh,
                             axis_name=agent_ax, model_axis=model_ax,
                             jit=jit, **kw)
            return make

        make_step = _sharded(sharded_lib.make_sharded_feddec_step)
        make_round = _sharded(sharded_lib.make_sharded_feddec_round)
        name += ":sharded"
    elif state_layout == "flat":
        spec = flat_lib.make_flat_spec(params_struct)
        state_struct = jax.eval_shape(
            lambda p: flat_lib.init_flat_state(spec, p, n_agents,
                                               compress=compress),
            params_struct)
        agent_ax = axes.data_axes if len(axes.data_axes) > 1 \
            else axes.data_axes[0]
        flat_spec_p = P(agent_ax, None) \
            if cfg.fed_agent_layout == "sharded" else P(None, None)
        state_specs = flat_lib.FlatFedState(
            flat=flat_spec_p, step=P(), opt_state=(),
            residual=() if compress == "none" else flat_spec_p)
        make_step = functools.partial(flat_lib.make_flat_feddec_step,
                                      fcfg, spec, grad_fn, lr_fn,
                                      fuse_update_mix=fuse_update_mix)
        make_round = functools.partial(flat_lib.make_flat_feddec_round,
                                       fcfg, spec, grad_fn, lr_fn,
                                       fuse_update_mix=fuse_update_mix)
        name += ":flat"
        if fuse_update_mix:
            name += ":updmix"
    else:
        state_specs = feddec.FedState(
            params=param_specs, step=P(), opt_state=(),
            residual=() if compress == "none" else param_specs)
        make_step = functools.partial(feddec.make_feddec_step,
                                      fcfg, grad_fn, lr_fn)
        make_round = functools.partial(feddec.make_feddec_round,
                                       fcfg, grad_fn, lr_fn)

    if fused_steps is None:
        step = make_step(gossip_fn=gossip_fn, jit=False)
    else:
        if fused_steps < 1:
            raise ValueError(f"fused_steps must be >= 1, got {fused_steps}")
        step = make_round(gossip_fn=gossip_fn, jit=False)
        # every batch leaf gains a leading fused-step dim, unsharded (the
        # scan consumes one slice per step)
        batch_struct = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((fused_steps,) + s.shape, s.dtype),
            batch_struct)
        batch_specs = jax.tree.map(lambda s: P(None, *s), batch_specs,
                                   is_leaf=lambda x: isinstance(x, P))
        name += f":fused{fused_steps}"

    key_struct = _key_struct()
    key_specs = P()
    if sweep_runs:
        if state_layout not in ("flat", "sharded"):
            raise ValueError("sweep_runs lowers the batched sweep engine "
                             "(repro.core.sweep); it requires "
                             "state_layout='flat' or 'sharded'")
        if fused_steps is None:
            raise ValueError("sweep_runs requires the fused executor "
                             "(fused_steps=H)")
        if gossip_fn is not None:
            raise ValueError("the sweep engine resolves gossip from "
                             "fed.gossip_impl; 'permute' gossip_fn "
                             "overrides are a single-run feature")
        plan = sweep_lib.make_sweep_plan(
            sweep_lattice_configs(fcfg, fed, sweep_runs, sweep_axis))
        state_struct = jax.eval_shape(
            lambda p: sweep_lib.init_sweep_state(plan, spec, p),
            params_struct)
        if state_layout == "sharded":
            if model_ax is not None:
                raise engine_lib.model_axis_conflict(
                    "sweep lattices (--sweep-runs) until the composition "
                    "lands")
            # the composed lowering: R runs × s agent shards, the whole
            # lattice scan inside one shard_map
            state_specs = engine_lib.sweep_state_specs(plan, spec,
                                                       axis_name=agent_ax)
            step = engine_lib.make_sharded_sweep_round(
                plan, spec, grad_fn, lr_fn, mesh, axis_name=agent_ax,
                jit=False)
        else:
            state_specs = sweep_lib.SweepFedState(
                flat=P(None, *flat_spec_p), step=P(None), opt_state=(),
                residual=() if compress == "none" else P(None, *flat_spec_p))
            step = sweep_lib.make_sweep_feddec_round(
                plan, spec, grad_fn, lr_fn, jit=False,
                fuse_update_mix=fuse_update_mix)
        # batches gain a run axis after the fused-step dim; keys become
        # the (R,) per-run key array
        batch_struct = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (s.shape[0], sweep_runs) + s.shape[1:], s.dtype),
            batch_struct)
        batch_specs = jax.tree.map(lambda s: P(None, *s), batch_specs,
                                   is_leaf=lambda x: isinstance(x, P))
        key_struct = jax.eval_shape(
            lambda: jax.random.split(jax.random.key(0), sweep_runs))
        key_specs = P(None)
        name += f":sweep{sweep_runs}-{sweep_axis}"

    return Lowerable(
        fn=step,
        args_struct=(state_struct, batch_struct, key_struct),
        in_specs=(state_specs, batch_specs, key_specs),
        out_specs=(state_specs, {"loss": P(), "eta": P()}),
        donate_argnums=(0,),
        name=name,
    )


def build_prefill_lowerable(cfg: ArchConfig, shape: ShapeConfig,
                            axes: shd.MeshAxes) -> Lowerable:
    """Inference prefill: full-sequence forward on serving params."""
    cfg = adapt_for_mesh(
        dataclasses.replace(cfg, param_dtype=jnp.bfloat16,
                            batch_axis_name="data"), axes)
    model = build_model(cfg)
    vocab_ok = cfg.vocab_size % axes.model_size == 0
    batch_ok = shape.global_batch % axes.data_size == 0
    dp_ax = axes.data_axes if len(axes.data_axes) > 1 else axes.data_axes[0]
    logits_cons = P(dp_ax if batch_ok else None, None,
                    axes.model_axis if vocab_ok else None)

    def prefill(params, batch):
        logits, _ = model.logits(params, batch, remat=False)
        # keep the (B, S, V) logits vocab-sharded: without this XLA
        # materialises a full-vocab f32 temp per device (~130 GB at a 262k
        # vocab) before the output resharding (§Perf iteration B3)
        return jax.lax.with_sharding_constraint(logits, logits_cons)

    params_struct = jax.eval_shape(model.init, jax.random.key(0))
    batch_struct = specs_lib._structs(specs_lib.batch_schema(
        cfg, None, shape.global_batch, shape.seq_len))
    param_specs = shd.serve_param_pspecs(cfg, params_struct, axes)
    batch_specs = shd.batch_pspecs(cfg, batch_struct, axes, stacked=False)
    dp = axes.data_axes if len(axes.data_axes) > 1 else axes.data_axes[0]
    logits_spec = P(dp if shape.global_batch % axes.data_size == 0 else None,
                    None,
                    axes.model_axis
                    if cfg.vocab_size % axes.model_size == 0 else None)

    return Lowerable(
        fn=prefill,
        args_struct=(params_struct, batch_struct),
        in_specs=(param_specs, batch_specs),
        out_specs=logits_spec,
        name=f"prefill:{cfg.name}:{shape.name}",
    )


def build_decode_lowerable(cfg: ArchConfig, shape: ShapeConfig,
                           axes: shd.MeshAxes) -> Lowerable:
    """One-token decode with a seq_len KV/state cache."""
    cfg = adapt_for_mesh(
        dataclasses.replace(cfg, param_dtype=jnp.bfloat16,
                            batch_axis_name="data"), axes)
    model = build_model(cfg)
    long_variant = shape.needs_subquadratic

    def serve_step(params, batch, caches):
        enc_out = batch.get("enc_out")
        core = {k: v for k, v in batch.items() if k != "enc_out"}
        logits, new_caches = model.decode_step(
            params, core, caches, enc_out=enc_out,
            long_variant=long_variant)
        next_tok = jnp.argmax(logits[:, -1], axis=-1)
        return next_tok, new_caches

    params_struct = jax.eval_shape(model.init, jax.random.key(0))
    batch_struct = specs_lib.decode_batch_specs(cfg, shape)
    caches_struct = jax.eval_shape(
        lambda: model.init_caches(shape.global_batch, shape.seq_len,
                                  long_variant=long_variant))

    param_specs = shd.serve_param_pspecs(cfg, params_struct, axes)
    batch_specs = shd.batch_pspecs(cfg, batch_struct, axes, stacked=False)
    cache_specs = shd.cache_pspecs(cfg, caches_struct, axes)
    dp = axes.data_axes if len(axes.data_axes) > 1 else axes.data_axes[0]
    tok_spec = P(dp if shape.global_batch % axes.data_size == 0 else None)

    return Lowerable(
        fn=serve_step,
        args_struct=(params_struct, batch_struct, caches_struct),
        in_specs=(param_specs, batch_specs, cache_specs),
        out_specs=(tok_spec, cache_specs),
        donate_argnums=(2,),
        name=f"decode:{cfg.name}:{shape.name}",
    )


def build_lowerable(cfg: ArchConfig, shape: ShapeConfig,
                    axes: shd.MeshAxes, **kw) -> Lowerable:
    if shape.kind == "train":
        return build_train_lowerable(cfg, shape, axes, **kw)
    kw.pop("fed", None), kw.pop("mesh", None), kw.pop("fused_steps", None)
    kw.pop("state_layout", None), kw.pop("mesh_model", None)
    kw.pop("sweep_runs", None), kw.pop("sweep_axis", None)
    kw.pop("fuse_update_mix", None)
    if shape.kind == "prefill":
        return build_prefill_lowerable(cfg, shape, axes)
    return build_decode_lowerable(cfg, shape, axes)
