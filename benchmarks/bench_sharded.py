"""Weak-scaling of the agent-sharded flat engine (repro.core.sharded).

The sharded engine block-shards the flat (n_agents, D) buffer's agent dim
over a device mesh axis; this benchmark measures, on 1/2/4/8 forced host
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the same
CPU recipe the multi-device CI job uses), at fixed D across
n_agents ∈ {8, 32, 128}:

  * ``dense``  — per-shard W[:, cols] @ x_blk + psum_scatter: collective
    bytes grow with n regardless of the graph;
  * ``sparse`` — the ppermute halo exchange over the ring graph's cut
    edges: 2 halo rounds per step at *any* n (the quotient of a ring over
    contiguous blocks is a ring), so per-device collective bytes stay flat
    as agents are added with devices — the weak-scaling win.

Every row carries measured wall-clock AND the analytic cost model
(launch.analysis.sharded_gossip_cost_model): on this CPU container the
collectives run over the host-platform loopback, so wall-clock ratios are
not ICI-representative — the transferable evidence is the per-device /
collective-byte columns and the cut-edge counts (cut_edge_stats).  Each
timed configuration is first checked against the unsharded dense einsum.

A second section times the full fused sharded round (H steps in one
shard_map'd lax.scan) on a quadratic workload, 1 vs 8 shards.

Emits the standard ``name,us_per_call,derived`` CSV lines plus
results/benchmarks/BENCH_sharded.json (consumed by CI's perf-regression
guard and docs/PERFORMANCE.md).

Run:  PYTHONPATH=src python -m benchmarks.bench_sharded [--smoke]

The benchmark re-executes itself in a subprocess with the forced-device-count
XLA flag so the parent process's jax device state is never touched (same
isolation pattern as tests/test_gossip_impls.py).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

N_DEVICES = 8


def main(smoke: bool = False) -> None:
    """Respawn into a forced-8-device subprocess and stream its output."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={N_DEVICES} "
                        + env.get("XLA_FLAGS", "")).strip()
    # a declared CPU simulation: the child never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")))
    cmd = [sys.executable, "-m", "benchmarks.bench_sharded", "--child"]
    if smoke:
        cmd.append("--smoke")
    res = subprocess.run(cmd, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    if res.returncode != 0:
        raise RuntimeError(f"bench_sharded child failed ({res.returncode})")


def _child_main(smoke: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks import common
    from repro.core import flat as flat_lib
    from repro.core import sharded, topology as topo
    from repro.core.feddec import FedDecConfig
    from repro.core.mixing import MixingDistribution
    from repro.launch import analysis
    from repro.launch.mesh import make_agent_mesh

    assert len(jax.devices()) >= N_DEVICES, "forced host devices missing"

    if smoke:
        warmup, iters = 1, 3
        d = 1 << 12
        agent_grid = (8, 32)
        round_cfg = dict(n=32, h=4)
    else:
        warmup, iters = 2, 5
        d = 1 << 16
        agent_grid = (8, 32, 128)
        round_cfg = dict(n=32, h=8)
    shard_grid = (1, 2, 4, 8)

    rows = []
    for n in agent_grid:
        graph = topo.ring_graph(n, k=2)
        md = MixingDistribution(graph, scheme="metropolis")
        w = jnp.asarray(md.sample(jax.random.key(0)))
        x_host = jax.random.normal(jax.random.key(1), (n, d), jnp.float32)
        ref = np.asarray(jnp.einsum(
            "ij,jd->id", w, x_host, precision=jax.lax.Precision.HIGHEST))
        for n_shards in shard_grid:
            if n % n_shards:
                continue
            mesh = make_agent_mesh(n_shards)
            x = jax.device_put(x_host, NamedSharding(mesh, P("agents")))
            cut = sharded.cut_edge_stats(graph, n_shards)
            model = analysis.sharded_gossip_cost_model(
                n_agents=n, d=d, n_shards=n_shards,
                num_cut_edges=cut["num_cut_edges"],
                num_halo_rounds=cut["num_halo_rounds"], param_bytes=4)
            for impl in ("dense", "sparse"):
                cfg = FedDecConfig(mixing=md, gossip_impl=impl)
                fn = jax.jit(sharded.make_sharded_gossip(cfg, mesh))
                np.testing.assert_allclose(np.asarray(fn(w, x)), ref,
                                           atol=1e-4, rtol=1e-4)
                us = common.time_fn(fn, w, x, warmup=warmup, iters=iters)
                cm = model[impl]
                row = {"impl": impl, "n_agents": n, "n_shards": n_shards,
                       "agents_per_device": n // n_shards, "d": d,
                       "us_per_call": round(us, 1),
                       "per_device_bytes": cm["per_device_bytes"],
                       "collective_bytes": cm["collective_bytes"],
                       "num_cut_edges": cut["num_cut_edges"],
                       "num_halo_rounds": cut["num_halo_rounds"]}
                rows.append(row)
                common.emit(
                    f"sharded_gossip_{impl}_n{n}_s{n_shards}", us,
                    f"coll_bytes={cm['collective_bytes']:.0f};"
                    f"cut={cut['num_cut_edges']}")

    # full fused round: H steps of grad + gossip + server in one shard_map
    n, h = round_cfg["n"], round_cfg["h"]
    graph = topo.ring_graph(n, k=2)
    md = MixingDistribution(graph, scheme="metropolis")
    spec = flat_lib.make_flat_spec(jnp.zeros(d))

    def grad_fn(p, batch, key):
        del key
        return 0.5 * jnp.sum((p - batch) ** 2), p - batch

    def lr_fn(t):
        return jnp.asarray(0.05, jnp.float32)

    batches = jax.random.normal(jax.random.key(3), (h, n, d), jnp.float32)
    key = jax.random.key(4)
    round_rows = []
    for n_shards in (1, N_DEVICES):
        mesh = make_agent_mesh(n_shards)
        cfg = FedDecConfig(mixing=md, h=h, k=2, gossip_impl="sparse")
        round_fn = sharded.make_sharded_feddec_round(
            cfg, spec, grad_fn, lr_fn, mesh, donate=False)
        state = sharded.shard_flat_state(
            flat_lib.init_flat_state(spec, jnp.zeros(d), n), mesh)
        us = common.time_fn(lambda: round_fn(state, batches, key),
                            warmup=warmup, iters=iters)
        round_rows.append({"n_agents": n, "n_shards": n_shards, "d": d,
                           "h": h, "us_per_round": round(us, 1),
                           "us_per_step": round(us / h, 1)})
        common.emit(f"sharded_round_n{n}_s{n_shards}_h{h}", us,
                    f"per_step={us / h:.1f}us")

    def us_of(impl, n, s):
        return next(r["us_per_call"] for r in rows
                    if (r["impl"], r["n_agents"], r["n_shards"])
                    == (impl, n, s))

    n_big = agent_grid[-1]
    full_sparse = [r for r in rows if r["n_shards"] == N_DEVICES
                   and r["impl"] == "sparse"]
    full_dense = {r["n_agents"]: r for r in rows if r["n_shards"] == N_DEVICES
                  and r["impl"] == "dense"}
    acceptance = {
        "weak_scaling_sparse_8dev": [
            {"n_agents": r["n_agents"],
             "collective_bytes_per_device": r["collective_bytes"],
             "us_per_call": r["us_per_call"]} for r in full_sparse],
        # the sharding story, per n at the full device count: the ring's
        # halo is 2 block rounds once agents_per_device ≥ 2 (the k=2 ring
        # quotients to a plain ring over blocks), so sparse collective
        # bytes per device are ~2/(s−1) of the dense psum_scatter's
        "halo_rounds_8dev": {str(r["n_agents"]): r["num_halo_rounds"]
                             for r in full_sparse},
        "collective_ratio_sparse_over_dense_8dev": {
            str(r["n_agents"]):
                round(r["collective_bytes"]
                      / full_dense[r["n_agents"]]["collective_bytes"], 3)
            for r in full_sparse},
        "speedup_sparse_over_dense_at_n_big":
            round(us_of("dense", n_big, N_DEVICES)
                  / us_of("sparse", n_big, N_DEVICES), 2),
        "equivalence_checked_vs_unsharded_dense": True,
        "note": ("CPU host-platform devices: collectives run over loopback "
                 "memory, so wall-clock is not ICI-representative; the "
                 "transferable evidence is collective_bytes / num_cut_edges "
                 "(analysis.sharded_gossip_cost_model at TPU constants) and "
                 "the 2/(s-1) sparse-over-dense collective-byte ratio once "
                 "agents_per_device >= 2"),
    }
    out = {"workload": "agent-sharded gossip y = W @ x, (n, D) buffer "
                       "block-sharded over the 'agents' mesh axis",
           "backend": jax.default_backend(), "smoke": smoke,
           "devices": N_DEVICES, "rows": rows, "round_rows": round_rows,
           "acceptance": acceptance}
    # smoke runs get their own file so a local/CI --smoke never clobbers
    # the committed full-run baseline the regression guard diffs against
    name = "BENCH_sharded.smoke.json" if smoke else "BENCH_sharded.json"
    path = os.path.join(common.ensure_results_dir(), name)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    common.write_csv("bench_sharded.csv", list(rows[0].keys()),
                     [tuple(r.values()) for r in rows])


if __name__ == "__main__":
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes / few iterations for CI")
    p.add_argument("--child", action="store_true",
                   help="internal: run the benchmark body (assumes the "
                        "forced-device XLA flag is already set)")
    args = p.parse_args()
    if args.child:
        _child_main(smoke=args.smoke)
    else:
        print("name,us_per_call,derived")
        main(smoke=args.smoke)
