"""End-to-end federated LM training driver (FedDec on real models).

Runs Algorithm 1 on any assigned architecture (reduced or full config) over
synthetic heterogeneous per-agent data streams, with checkpointing and an
optional FedAvg control arm.  On the host (CPU/1 device) it runs the
smoke-scale configs — the same code path as on the chip, smaller shapes.

Example (host scale):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --smoke \\
      --steps 100 --agents 8 --graph ring2 --h 10 --k 2
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro import optim
from repro.checkpoint import save_checkpoint
from repro.configs import get_config
from repro.configs.base import ArchConfig, FedConfig
from repro.core import engine as engine_lib
from repro.core import feddec
from repro.core import flat as flat_lib
from repro.core import population as population_lib
from repro.core import sharded as sharded_lib
from repro.core import sweep as sweep_lib
from repro.core import topology as topo
from repro.core.fedavg import FedAvgConfig
from repro.data.federated_lm import make_federated_lm
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_agent_mesh, make_fed_mesh
from repro.launch.steps import build_fed_setup, sweep_lattice_configs
from repro.models import build_model
from repro.sharding import MeshAxes

__all__ = ["train_loop", "population_loop", "tiny_lm_config",
           "population_graph"]


def tiny_lm_config(d_model: int = 768, layers: int = 12,
                   vocab: int = 32_768, name: str = "tiny-lm") -> ArchConfig:
    """A ~100M-parameter dense LM for the end-to-end example."""
    return ArchConfig(
        name=name, arch_type="dense", source="examples",
        num_layers=layers, d_model=d_model, num_heads=d_model // 64,
        num_kv_heads=max(1, d_model // 128), d_ff=4 * d_model,
        vocab_size=vocab, mlp_kind="swiglu",
        param_dtype=jnp.float32, compute_dtype=jnp.float32)


def train_loop(cfg: ArchConfig, fed: FedConfig, *, steps: int,
               per_agent_batch: int, seq_len: int, lr: float = 3e-3,
               optimizer: str = "sgd", fedavg_control: bool = False,
               fused: bool = True, state_layout: str | None = None,
               fuse_update_mix: bool = False,
               mesh_agents: int | None = None,
               mesh_model: int | None = None,
               sweep_runs: int | None = None, sweep_axis: str = "seed",
               ckpt_dir: str | None = None, ckpt_every: int = 0,
               log_every: int = 10, seed: int = 0,
               data_alpha: float = 0.3):
    """Run FedDec training; returns (final_state, loss_history).

    ``fused=True`` (default) executes one compiled ``lax.scan`` per
    inter-server-round window of H steps (repro.core.feddec.make_feddec_round)
    — one dispatch per round instead of per step.  ``fused=False`` keeps the
    per-step executor for debugging (inspect state between every iteration).
    When ``steps`` is not a multiple of H the trailing short round compiles a
    second scan (shorter leading batch dim) — a one-off cost; keep ``steps``
    a multiple of H to avoid it.

    ``state_layout`` selects the carried-state engine: ``'flat'`` runs
    Algorithm 1 on the single contiguous (n_agents, D) buffer
    (repro.core.flat — whole-buffer SGD/gossip/server ops, the hot-loop
    default for the fused path), ``'tree'`` keeps the per-leaf pytree
    engine.  ``None`` picks ``'flat'`` when fused, ``'tree'`` per-step.
    The returned state is always a tree-engine ``FedState``.  The gossip
    execution path comes from ``fed.gossip_impl``
    (dense|pallas|sparse|none).

    ``mesh_agents=N`` runs the device-sharded engine (repro.core.sharded):
    the flat (n_agents, D) buffer is block-sharded over an N-device
    ``agents`` mesh axis (n_agents must be divisible by N) and gossip /
    server rounds execute as psum_scatter / ppermute-halo / psum
    collectives.  Implies the flat layout.  On CPU force host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

    ``sweep_runs=R`` runs R independent FedDec replicas batched into one
    (R, n_agents, D) program (repro.core.sweep), varying ``sweep_axis``
    per run: 'seed' (per-run PRNG keys), 'h' (doubling server periods), or
    'topology' (independent graph draws).  All runs share the data stream;
    losses are averaged over the lattice per step and per-run finals are
    printed.  Implies the flat layout and the fused executor; the returned
    FedState is run 0's.  Checkpointing a lattice is not supported.

    ``mesh_model=M`` (with ``mesh_agents=A``) runs the 2-D engine on a
    ``make_fed_mesh(A, M)`` ('agents', 'model') mesh: each agent replica's
    D-dim state is additionally column-sharded over M devices (per-device
    bytes ``n/A · D/M · 4``) while gossip / server collectives stay on the
    agent axis.  Incoherent combinations (--delta, tree layout,
    --sweep-runs) raise the canonical model-axis ValueError up front.

    ``sweep_runs=R`` composes with ``mesh_agents=s``: the whole lattice
    lowers as one (R, n_agents/s, D)-per-device program
    (repro.core.engine.make_sharded_sweep_round) — the agent dim of every
    run is block-sharded over the ``agents`` mesh axis and the full T-step
    scan runs inside one shard_map, so the per-step collectives are the
    only cross-device traffic of the entire figure lattice.  Every run
    slice matches the single-run flat engine to ≤ 1e-5.

    Tracing: ``with jax.profiler.trace(log_dir): train_loop(...)`` records
    each fused round as a ``feddec.round`` step (``StepTraceAnnotation``,
    ``step_num`` = the round), with the host spans ``feddec.sample`` (the
    token draw), ``feddec.loss_pull`` (the wait for the round's losses) and
    ``feddec.ckpt`` inside it; the device ops carry the engine's
    ``feddec.*`` phase scopes in their ``op_name``.  The logged steps/s
    counts from the end of the first round, which compiles.
    """
    model = build_model(cfg)
    axes = MeshAxes(("data",), "model", {"data": fed.n_agents, "model": 1})
    fcfg, n_agents = build_fed_setup(cfg, axes, fed)
    if fedavg_control:
        fcfg = FedAvgConfig(n_agents, h=fed.h, k=fed.k)
    if state_layout is None:
        state_layout = "flat" if fused or mesh_agents else "tree"
    if state_layout not in ("tree", "flat"):
        raise ValueError(f"state_layout must be 'tree' or 'flat', "
                         f"got {state_layout!r}")
    if mesh_model is not None and mesh_model > 1:
        # the canonical model-axis compatibility lattice — identical
        # messages to parse_engine_spec's (engine.model_axis_conflict)
        if mesh_agents is None:
            raise ValueError("--mesh-model needs --mesh-agents (the model "
                             "axis extends the agent mesh to 2-D)")
        if state_layout != "flat":
            raise engine_lib.model_axis_conflict(
                "layout 'tree' (the pytree engine has no flat buffer to "
                "column-shard)")
        if sweep_runs is not None:
            raise engine_lib.model_axis_conflict(
                "sweep lattices (--sweep-runs) until the composition lands")
        if (getattr(fcfg, "gossip_impl", "none") != "none"
                and getattr(fcfg, "delta", "none") != "none"):
            raise engine_lib.model_axis_conflict(
                "delta parameterization (--delta)")
    if mesh_agents is not None and state_layout != "flat":
        raise ValueError("--mesh-agents shards the flat (n_agents, D) "
                         "buffer; it requires --state-layout flat")
    if fuse_update_mix:
        # same compatibility lattice as parse_engine_spec's
        if state_layout != "flat":
            raise ValueError("--fuse-update-mix fuses the whole-buffer "
                             "update+mix pass (kernels/update_mix.py); it "
                             "requires --state-layout flat")
        if mesh_agents is not None:
            raise ValueError("--fuse-update-mix is single-device: the "
                             "sharded engine overlaps its halo with "
                             "interior compute instead (core/sharded.py); "
                             "drop --mesh-agents")
    if sweep_runs is not None:
        if not fused:
            raise ValueError("--sweep-runs requires the fused executor")
        if state_layout != "flat":
            raise ValueError("--sweep-runs batches the flat (n_agents, D) "
                             "buffer; it requires --state-layout flat")
        if ckpt_dir:
            raise ValueError("checkpointing a sweep lattice is not "
                             "supported; run without --ckpt-dir")

    opt = {"sgd": None, "momentum": optim.momentum_sgd(),
           "adamw": optim.adamw()}[optimizer]
    lr_fn = lambda t: jnp.asarray(lr, jnp.float32)  # noqa: E731
    # no exchange (FedAvg / impl 'none') ⇒ nothing to compress, no residual
    compress = fcfg.gossip_compress if fcfg.gossip_impl != "none" else "none"
    delta = fcfg.delta if fcfg.gossip_impl != "none" else "none"

    data = make_federated_lm(cfg.vocab_size, n_agents, seq_len,
                             alpha=data_alpha, seed=seed)
    params0 = model.init(jax.random.key(seed))
    spec = None
    if state_layout == "flat":
        spec = flat_lib.make_flat_spec(params0)
        if sweep_runs is not None:
            plan = sweep_lib.make_sweep_plan(
                sweep_lattice_configs(fcfg, fed, sweep_runs, sweep_axis))
            state = sweep_lib.init_sweep_state(plan, spec, params0,
                                               optimizer=opt)
            if mesh_agents is not None:
                # composed lowering: R runs × s agent shards, one program
                if n_agents % mesh_agents:
                    raise ValueError(f"--mesh-agents {mesh_agents} must "
                                     f"divide --agents {n_agents}")
                mesh = make_agent_mesh(mesh_agents)
                state = engine_lib.shard_sweep_state(state, mesh)
                round_fn = engine_lib.make_sharded_sweep_round(
                    plan, spec, model.grad_fn(), lr_fn, mesh,
                    optimizer=opt, donate=True)
            else:
                round_fn = sweep_lib.make_sweep_feddec_round(
                    plan, spec, model.grad_fn(), lr_fn, optimizer=opt,
                    donate=True, fuse_update_mix=fuse_update_mix)
        else:
            state = flat_lib.init_flat_state(spec, params0, n_agents,
                                             optimizer=opt,
                                             compress=compress,
                                             delta=delta)
            if mesh_agents is not None:
                if n_agents % mesh_agents:
                    raise ValueError(f"--mesh-agents {mesh_agents} must "
                                     f"divide --agents {n_agents}")
                model_ax = "model" if mesh_model and mesh_model > 1 \
                    else None
                mesh = make_fed_mesh(mesh_agents, mesh_model) \
                    if model_ax else make_agent_mesh(mesh_agents)
                state = sharded_lib.shard_flat_state(state, mesh,
                                                     model_axis=model_ax)
                # the chunked-prefill scan cannot cross the 2-D engine's
                # partially-auto region (ArchConfig.attn_chunked_prefill)
                grad = model.grad_fn() if model_ax is None else build_model(
                    dataclasses.replace(
                        cfg, attn_chunked_prefill=False)).grad_fn()
                if fused:
                    round_fn = sharded_lib.make_sharded_feddec_round(
                        fcfg, spec, grad, lr_fn, mesh,
                        optimizer=opt, donate=True, model_axis=model_ax)
                else:
                    step = sharded_lib.make_sharded_feddec_step(
                        fcfg, spec, grad, lr_fn, mesh,
                        optimizer=opt, donate=True, model_axis=model_ax)
            elif fused:
                round_fn = flat_lib.make_flat_feddec_round(
                    fcfg, spec, model.grad_fn(), lr_fn, optimizer=opt,
                    donate=True, delta_base=spec.ravel(params0)
                    if delta != "none" else None,
                    fuse_update_mix=fuse_update_mix)
            else:
                step = flat_lib.make_flat_feddec_step(
                    fcfg, spec, model.grad_fn(), lr_fn, optimizer=opt,
                    donate=True, delta_base=spec.ravel(params0)
                    if delta != "none" else None,
                    fuse_update_mix=fuse_update_mix)
    else:
        state = feddec.init_state(params0, n_agents, optimizer=opt,
                                  compress=compress)
        if fused:
            round_fn = feddec.make_feddec_round(
                fcfg, model.grad_fn(), lr_fn, optimizer=opt, donate=True)
        else:
            step = feddec.make_feddec_step(
                fcfg, model.grad_fn(), lr_fn, optimizer=opt, donate=True)

    def ckpt_params(st):
        return spec.unflatten(st.flat) if state_layout == "flat" \
            else st.params

    print(f"[train] {cfg.name}: {model.param_count(params0):,} params × "
          f"{n_agents} agents, graph={fed.graph}, H={fed.h}, K={fcfg.k}, "
          f"opt={optimizer}, executor={'fused' if fused else 'per-step'}, "
          f"layout={state_layout}"
          + (f" (sharded over {mesh_agents} devices)"
             if mesh_agents and not (mesh_model and mesh_model > 1) else "")
          + (f" (2-D mesh: {mesh_agents} agents x {mesh_model} model)"
             if mesh_agents and mesh_model and mesh_model > 1 else "")
          + (f" (sweep lattice R={sweep_runs} axis={sweep_axis})"
             if sweep_runs else "")
          + f", gossip={fcfg.gossip_impl}"
          + (", fused-update-mix" if fuse_update_mix else "")
          + (f", compress={compress}" if compress != "none" else "")
          + (f", delta={delta}" if delta != "none" else ""))

    positions = jnp.broadcast_to(
        jnp.arange(seq_len, dtype=jnp.int32)[None, None],
        (n_agents, per_agent_batch, seq_len))
    key = jax.random.key(seed + 1)
    step_key = jax.random.key(seed + 2)
    if sweep_runs is not None:
        # 'seed' lattices decorrelate per-run keys; 'h'/'topology' keep the
        # key stream identical so the axis is the only difference
        run_keys = jax.vmap(
            lambda r: jax.random.fold_in(step_key, r))(
            jnp.arange(sweep_runs)) if sweep_axis == "seed" else \
            jnp.broadcast_to(step_key[None], (sweep_runs,))
    losses = []
    # steps done and clock at the end of the first round (or step), which
    # compiles: the logged rate counts from there
    first = {}

    def log_and_ckpt(prev: int, done: int) -> None:
        if not first:
            first.update(done=done, t=time.perf_counter())
        # fire when a multiple of the period falls in (prev, done] — a fused
        # round advances h steps at once and must not skip boundaries
        if log_every and done // log_every > prev // log_every:
            since = done - first["done"]
            rate = (f"{since / (time.perf_counter() - first['t']):.2f} "
                    f"steps/s" if since else "first round, compiled")
            print(f"[train] step {done:5d}  loss {losses[-1]:.4f}  "
                  f"({rate})")
        if (ckpt_dir and ckpt_every
                and done // ckpt_every > prev // ckpt_every):
            with TraceAnnotation("feddec.ckpt"):
                save_checkpoint(ckpt_dir, done,
                                {"params": ckpt_params(state),
                                 "step": state.step})

    if fused:
        done = 0
        while done < steps:
            with StepTraceAnnotation("feddec.round",
                                     step_num=done // fed.h):
                chunk = min(fed.h, steps - done)
                with TraceAnnotation("feddec.sample"):
                    key, kd = jax.random.split(key)
                    tokens = jax.vmap(
                        lambda k: data.sample(k, per_agent_batch))(
                        jax.random.split(kd, chunk))
                    batches = {"tokens": tokens,
                               "positions": jnp.broadcast_to(
                                   positions[None],
                                   (chunk,) + positions.shape)}
                    if sweep_runs is not None:
                        # shared data stream, one (chunk, R, ...) round
                        batches = jax.tree.map(
                            lambda b: jnp.broadcast_to(
                                b[:, None],
                                (b.shape[0], sweep_runs) + b.shape[1:]),
                            batches)
                state, metrics = round_fn(
                    state, batches,
                    step_key if sweep_runs is None else run_keys)
                with TraceAnnotation("feddec.loss_pull"):
                    loss = metrics["loss"]
                    if sweep_runs is not None:
                        loss = loss.mean(axis=1)  # the lattice's mean
                    losses.extend(np.asarray(loss).tolist())
                done += chunk
                log_and_ckpt(done - chunk, done)
    else:
        for i in range(steps):
            with TraceAnnotation("feddec.sample"):
                key, kd = jax.random.split(key)
                tokens = data.sample(kd, per_agent_batch)
            batch = {"tokens": tokens, "positions": positions}
            state, metrics = step(state, batch, step_key)
            with TraceAnnotation("feddec.loss_pull"):
                losses.append(float(metrics["loss"]))
            log_and_ckpt(i, i + 1)
    if ckpt_dir:
        with TraceAnnotation("feddec.ckpt"):
            save_checkpoint(ckpt_dir, steps,
                            {"params": ckpt_params(state),
                             "step": state.step})
    if sweep_runs is not None:
        finals = np.asarray(metrics["loss"][-1])
        print("[train] sweep finals (last-step loss per run): "
              + ", ".join(f"r{r}={v:.4f}" for r, v in enumerate(finals)))
        state = sweep_lib.slice_run(state, 0)
    if state_layout == "flat":
        state = flat_lib.unflatten_fedstate(spec, state)
    return state, losses


def population_graph(name: str, n_total: int) -> topo.SparseGraph:
    """Parse a population-scale graph spec — CSR only, never dense.

    Only the ring family scales to n_total = 1e6 without a dense draw;
    'ring<k>' (e.g. ring2) maps to :func:`topology.ring_graph_csr`.
    """
    if name.startswith("ring"):
        k = int(name[4:]) if name[4:] else 1
        return topo.ring_graph_csr(n_total, k)
    raise ValueError(
        f"population mode needs a CSR-scalable graph family; got "
        f"{name!r} (supported: ring<k>)")


def population_loop(cfg: ArchConfig, fed: FedConfig, *, n_total: int,
                    cohort_size: int, sampling: str = "uniform",
                    staleness: float = 0.0, n_clusters: int = 0,
                    steps: int, per_agent_batch: int, seq_len: int,
                    lr: float = 3e-3, ckpt_dir: str | None = None,
                    overlap: bool = True, seed: int = 0,
                    data_alpha: float = 0.3):
    """Cohort-streamed FedDec over an n_total-agent population.

    The population rows live in a host memmap (repro.core.population);
    each fused H-step round trains one ``cohort_size`` cohort, with the
    next cohort's rows / subgraph / data batch prepared while the current
    round executes on device (``overlap=True``).  Returns
    ``(store, loss_history)`` — the store holds every agent's final rows.

    The per-agent LM data table is (n_total, vocab), so LM population runs
    target n_total ≲ 1e5; the 1e6 regime needs linreg-scale D, with the
    data stream generated per cohort.
    """
    if steps % fed.h:
        raise ValueError(f"population mode runs whole H-step rounds; "
                         f"--steps {steps} must be a multiple of --h "
                         f"{fed.h}")
    model = build_model(cfg)
    graph = population_graph(fed.graph, n_total)
    pspec = population_lib.PopulationSpec(
        n_total=n_total, cohort_size=cohort_size, sampling=sampling,
        staleness=staleness, max_degree=graph.max_degree,
        n_clusters=n_clusters, seed=seed)
    if fed.gossip_compress != "none":
        raise ValueError("population mode streams uncompressed rows; "
                         "--gossip-compress is not supported")
    # --delta in population mode is a *storage* format: the host store
    # keeps encoded delta rows (repro.core.delta.DeltaStore) and the
    # cohort gossip runs on the decoded dense rows; 'full' is lossless
    data = make_federated_lm(cfg.vocab_size, n_total, seq_len,
                             alpha=data_alpha, seed=seed)
    params0 = model.init(jax.random.key(seed))
    spec = flat_lib.make_flat_spec(params0)
    lr_fn = lambda t: jnp.asarray(lr, jnp.float32)  # noqa: E731
    eng = population_lib.PopulationEngine(
        pspec, spec, model.grad_fn(), lr_fn, graph, h=fed.h, k=fed.k,
        row_init=np.asarray(spec.ravel(params0)), delta=fed.delta)
    print(f"[train] population: {model.param_count(params0):,} params × "
          f"n_total={n_total} (cohort {cohort_size}, sampling={sampling}"
          + (f", staleness={staleness}" if staleness else "")
          + (f", clusters={n_clusters}" if n_clusters > 1 else "")
          + f"), graph={fed.graph}, H={fed.h}, K={fed.k}, "
          + (f"delta={fed.delta}, " if fed.delta != "none" else "")
          + f"store={eng.store.nbytes / 1e6:.1f} MB host-side")

    positions = jnp.broadcast_to(
        jnp.arange(seq_len, dtype=jnp.int32)[None, None],
        (cohort_size, per_agent_batch, seq_len))
    data_key = jax.random.key(seed + 1)

    def batch_fn(round_idx: int, ids: np.ndarray):
        kd = jax.random.fold_in(data_key, round_idx)
        ids_j = jnp.asarray(ids, dtype=jnp.int32)

        def per_step(k):
            ks = jax.random.split(k, ids_j.shape[0])
            return jax.vmap(data.sample_agent, in_axes=(0, 0, None))(
                ks, ids_j, per_agent_batch)

        tokens = jax.vmap(per_step)(jax.random.split(kd, fed.h))
        return {"tokens": tokens,
                "positions": jnp.broadcast_to(
                    positions[None], (fed.h,) + positions.shape)}

    t_start = time.time()
    mets = eng.run(steps // fed.h, batch_fn, jax.random.key(seed + 2),
                   overlap=overlap)
    losses = np.asarray(mets["loss"]).reshape(-1).tolist()
    rate = steps / (time.time() - t_start)
    print(f"[train] population: {steps} steps in "
          f"{steps // fed.h} rounds ({rate:.2f} steps/s, "
          f"{mets['drains']} pipeline drains)")
    if ckpt_dir:
        eng.store.save(ckpt_dir, steps)
    return eng.store, losses


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="tiny",
                   help="assigned arch id, or 'tiny' for the ~100M LM")
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced smoke variant of --arch")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--agents", type=int, default=8)
    p.add_argument("--batch", type=int, default=2,
                   help="per-agent batch size")
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--graph", default="ring2")
    p.add_argument("--h", type=int, default=10)
    p.add_argument("--k", type=int, default=2)
    p.add_argument("--p-fail", type=float, default=0.0)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--optimizer", default="sgd",
                   choices=["sgd", "momentum", "adamw"])
    p.add_argument("--fedavg", action="store_true",
                   help="run the FedAvg control instead of FedDec")
    ex = p.add_mutually_exclusive_group()
    ex.add_argument("--fused", dest="fused", action="store_true",
                    default=True,
                    help="fused executor: one lax.scan per H-step round "
                         "(default)")
    ex.add_argument("--per-step", dest="fused", action="store_false",
                    help="one jitted call per iteration (debugging)")
    p.add_argument("--state-layout", default=None,
                   choices=["tree", "flat"],
                   help="carried-state engine: 'flat' = single (n, D) "
                        "buffer hot loop (default when fused), 'tree' = "
                        "per-leaf pytree engine (default per-step)")
    p.add_argument("--gossip-impl", default="dense",
                   choices=["dense", "pallas", "sparse", "none"],
                   help="how the gossip mix executes (Algorithm 1 line 6)")
    p.add_argument("--fuse-update-mix", action="store_true",
                   help="fuse Algorithm 1 lines 5-6 (optimizer update + "
                        "gossip mix, + EF correction under a codec) into "
                        "one tiled buffer pass (kernels/update_mix.py); "
                        "flat/sweep layouts, sgd/momentum (adamw falls "
                        "back to the unfused pair)")
    p.add_argument("--gossip-compress", default="none", metavar="SPEC",
                   help="compress the gossip payload with error feedback "
                        "(repro.core.compress): none | identity | bf16 | "
                        "int8 | topk:R (e.g. topk:0.1); the sharded "
                        "engine's ppermute halo then moves the encoded "
                        "payload")
    p.add_argument("--delta", default="none", metavar="SPEC",
                   help="delta-parameterize the agent state against a "
                        "shared base row (repro.core.delta): none | full | "
                        "topk:K | lowrank:R (e.g. topk:128).  Gossip then "
                        "exchanges encoded deltas with error feedback "
                        "('full' is lossless — bit-identical to none); in "
                        "population mode (--n-total) the host store keeps "
                        "encoded delta rows, O(n_total·K) bytes.  Mutually "
                        "exclusive with --gossip-compress")
    p.add_argument("--mesh-agents", type=int, default=None, metavar="N",
                   help="shard the flat (n_agents, D) buffer over an "
                        "N-device 'agents' mesh axis (repro.core.sharded); "
                        "composes with --gossip-impl and --fused.  On CPU: "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    p.add_argument("--mesh-model", type=int, default=None, metavar="M",
                   help="with --mesh-agents A, extend the mesh to 2-D "
                        "(launch.mesh.make_fed_mesh(A, M)): each agent "
                        "replica's D-dim state is column-sharded over M "
                        "'model'-axis devices (per-device bytes n/A*D/M*4) "
                        "while gossip/server collectives stay on 'agents'. "
                        "Does not compose with --delta or --sweep-runs")
    p.add_argument("--sweep-runs", type=int, default=None, metavar="R",
                   help="run R independent FedDec replicas batched into "
                        "one (R, n_agents, D) program (repro.core.sweep); "
                        "losses are lattice-averaged, per-run finals "
                        "printed.  Composes with --mesh-agents s: the "
                        "lattice lowers as one (R, n_agents/s, D)-per-"
                        "device shard_map program "
                        "(repro.core.engine.make_sharded_sweep_round)")
    p.add_argument("--sweep-axis", default="seed",
                   choices=["seed", "h", "topology"],
                   help="what varies across the --sweep-runs lattice: "
                        "per-run PRNG keys (seed), doubling server "
                        "periods H·2^r (h), or independent graph draws "
                        "(topology; geo/er families)")
    p.add_argument("--n-total", type=int, default=None, metavar="N",
                   help="population mode (repro.core.population): keep N "
                        "agents in a host memmap store and train a sampled "
                        "cohort per fused round, streaming rows h2d/d2h "
                        "double-buffered.  Overrides --agents; requires a "
                        "ring<k> graph and the stateless sgd optimizer")
    p.add_argument("--cohort-size", type=int, default=64, metavar="C",
                   help="agents sampled + streamed per round in population "
                        "mode")
    p.add_argument("--sampling", default="uniform",
                   choices=list(population_lib.SAMPLINGS),
                   help="population cohort sampler: uniform, weighted "
                        "(per-agent weights), or stale (prioritize agents "
                        "longest out of a cohort)")
    p.add_argument("--staleness", type=float, default=0.0, metavar="BETA",
                   help="FedPAE-style age tilt of the cohort mixing matrix "
                        "(0 = plain doubly stochastic Metropolis)")
    p.add_argument("--n-clusters", type=int, default=0, metavar="M",
                   help="population mode: M > 1 enables the two-tier "
                        "hierarchical server round (edge-cluster averaging "
                        "before the K-sample aggregation)")
    p.add_argument("--no-overlap", dest="overlap", action="store_false",
                   default=True,
                   help="population mode: disable the double-buffered "
                        "h2d/d2h overlap (synchronous transfers; same "
                        "trajectory, slower)")
    p.add_argument("--vocab", type=int, default=32_768,
                   help="tiny-LM vocab size (population mode keeps an "
                        "(n_total, vocab) data table — shrink this for "
                        "large --n-total smokes)")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--d-model", type=int, default=768)
    p.add_argument("--layers", type=int, default=12)
    args = p.parse_args()
    enable_compile_cache()

    if args.arch == "tiny":
        cfg = tiny_lm_config(args.d_model, args.layers, vocab=args.vocab)
    else:
        cfg = get_config(args.arch)
        if args.smoke:
            cfg = cfg.smoke()
    fed = FedConfig(n_agents=args.agents, h=args.h, k=args.k,
                    graph=args.graph, p_fail=args.p_fail,
                    gossip_impl=args.gossip_impl,
                    gossip_compress=args.gossip_compress,
                    delta=args.delta)
    if args.n_total is not None:
        for flag, val, default in (("--mesh-agents", args.mesh_agents, None),
                                   ("--mesh-model", args.mesh_model, None),
                                   ("--sweep-runs", args.sweep_runs, None),
                                   ("--fuse-update-mix",
                                    args.fuse_update_mix, False),
                                   ("--optimizer", args.optimizer, "sgd"),
                                   ("--fedavg", args.fedavg, False),
                                   ("--per-step", args.fused, True)):
            if val != default:
                raise SystemExit(f"population mode (--n-total) does not "
                                 f"compose with {flag}")
        _, losses = population_loop(
            cfg, fed, n_total=args.n_total, cohort_size=args.cohort_size,
            sampling=args.sampling, staleness=args.staleness,
            n_clusters=args.n_clusters, steps=args.steps,
            per_agent_batch=args.batch, seq_len=args.seq, lr=args.lr,
            ckpt_dir=args.ckpt_dir, overlap=args.overlap)
        first = np.mean(losses[:5])
        last = np.mean(losses[-5:])
        print(f"[train] done: loss {first:.4f} → {last:.4f} "
              f"({'improved' if last < first else 'NO IMPROVEMENT'})")
        return
    state, losses = train_loop(
        cfg, fed, steps=args.steps, per_agent_batch=args.batch,
        seq_len=args.seq, lr=args.lr, optimizer=args.optimizer,
        fedavg_control=args.fedavg, fused=args.fused,
        state_layout=args.state_layout,
        fuse_update_mix=args.fuse_update_mix,
        mesh_agents=args.mesh_agents,
        mesh_model=args.mesh_model,
        sweep_runs=args.sweep_runs, sweep_axis=args.sweep_axis,
        ckpt_dir=args.ckpt_dir)
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    print(f"[train] done: loss {first:.4f} → {last:.4f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
