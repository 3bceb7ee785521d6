"""2-D mesh scaling of the model-sharded flat engine (repro.core.sharded).

The 2-D ('agents', 'model') lowering (launch.mesh.make_fed_mesh) block-
shards the flat (n_agents, D) buffer's agent dim over A devices AND
column-shards each agent row's D dim over M devices, so per-device state
scales as 1/(A·M) — the memory axis that lets billion-parameter agents fit.
This benchmark measures, on 8 forced host devices (the multi-device CI
recipe), a fused H-step FedDec round over the mesh grid
(A, M) ∈ {(1,1), (8,1), (4,2), (2,4), (1,8)} for the dense / sparse /
pallas gossip paths:

  * measured per-device shard bytes — asserted EQUAL to the analytic
    ``n/A · D/M · param_bytes`` (the 1/(A·M) scaling law, exact, not
    approximate: the engine pins P('agents', 'model') on every 2-D leaf);
  * the full mesh2d_cost_model byte columns (agent-axis gossip bytes on
    D/M-wide slices, model-axis loss/matmul collective bytes, server psum
    bytes) recorded per row for the regression guard to recompute;
  * wall-clock per fused round (CPU loopback — not ICI-representative;
    the transferable evidence is the byte columns, same caveat as
    bench_sharded).

Every (A, M) cell is first checked against the single-device flat engine's
trajectory to 1e-5 (the conformance tolerance), so the numbers always
describe a correct lowering.

Emits the standard ``name,us_per_call,derived`` CSV lines plus
results/benchmarks/BENCH_mesh2d.json (consumed by CI's perf-regression
guard and docs/PERFORMANCE.md).

Run:  PYTHONPATH=src python -m benchmarks.bench_mesh2d [--smoke]

Re-executes itself in a forced-8-device subprocess so the parent's jax
device state is never touched (same pattern as bench_sharded).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

N_DEVICES = 8

MESH_GRID = ((1, 1), (8, 1), (4, 2), (2, 4), (1, 8))
IMPLS = ("dense", "sparse", "pallas")


def main(smoke: bool = False) -> None:
    """Respawn into a forced-8-device subprocess and stream its output."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={N_DEVICES} "
                        + env.get("XLA_FLAGS", "")).strip()
    # a declared CPU simulation: the child never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")))
    cmd = [sys.executable, "-m", "benchmarks.bench_mesh2d", "--child"]
    if smoke:
        cmd.append("--smoke")
    res = subprocess.run(cmd, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    if res.returncode != 0:
        raise RuntimeError(f"bench_mesh2d child failed ({res.returncode})")


def _child_main(smoke: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import common
    from repro.core import flat as flat_lib
    from repro.core import sharded, topology as topo
    from repro.core.feddec import FedDecConfig
    from repro.core.mixing import MixingDistribution
    from repro.launch import analysis
    from repro.launch.mesh import make_fed_mesh

    assert len(jax.devices()) >= N_DEVICES, "forced host devices missing"

    if smoke:
        warmup, iters = 1, 3
        n, d, h = 8, 1 << 10, 2
    else:
        warmup, iters = 2, 5
        n, d, h = 32, 1 << 14, 4

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

    rows = []
    n_equiv_checked = 0
    for impl in IMPLS:
        cfg = FedDecConfig(mixing=md, h=h, k=2, gossip_impl=impl)
        # the single-device flat reference this impl's cells must match
        ref_round = flat_lib.make_flat_feddec_round(
            cfg, spec, grad_fn, lr_fn, donate=False)
        ref_state, ref_m = ref_round(
            flat_lib.init_flat_state(spec, jnp.zeros(d), n), batches, key)
        ref_flat = np.asarray(ref_state.flat)
        ref_loss = np.asarray(ref_m["loss"])

        for a, m in MESH_GRID:
            if n % a or d % m:
                continue
            mesh = make_fed_mesh(a, m)
            cut = sharded.cut_edge_stats(graph, a)
            model = analysis.mesh2d_cost_model(
                n_agents=n, d=d, n_agent_shards=a, n_model_shards=m,
                num_halo_rounds=cut["num_halo_rounds"], param_bytes=4)[impl]
            round_fn = sharded.make_sharded_feddec_round(
                cfg, spec, grad_fn, lr_fn, mesh, donate=False,
                model_axis="model")
            state0 = sharded.shard_flat_state(
                flat_lib.init_flat_state(spec, jnp.zeros(d), n), mesh,
                model_axis="model")
            out_state, out_m = round_fn(state0, batches, key)
            np.testing.assert_allclose(np.asarray(out_state.flat), ref_flat,
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(np.asarray(out_m["loss"]), ref_loss,
                                       atol=1e-5, rtol=1e-5)
            n_equiv_checked += 1
            shard_bytes = out_state.flat.addressable_shards[0].data.nbytes
            us = common.time_fn(lambda: round_fn(state0, batches, key),
                                warmup=warmup, iters=iters)
            row = {"impl": impl, "n_agents": n, "d": d, "h": h,
                   "n_agent_shards": a, "n_model_shards": m,
                   "agents_per_device": n // a,
                   "us_per_round": round(us, 1),
                   "us_per_step": round(us / h, 1),
                   "shard_bytes_measured": int(shard_bytes),
                   "state_bytes_per_device": model["state_bytes_per_device"],
                   "gossip_collective_bytes":
                       model["gossip_collective_bytes"],
                   "model_collective_bytes": model["model_collective_bytes"],
                   "server_bytes_per_round": model["server_bytes_per_round"],
                   "num_halo_rounds": cut["num_halo_rounds"]}
            assert shard_bytes == model["state_bytes_per_device"], row
            rows.append(row)
            common.emit(
                f"mesh2d_{impl}_a{a}_m{m}", us,
                f"shard_bytes={shard_bytes};"
                f"model_coll={model['model_collective_bytes']:.0f}")

    base_bytes = n * d * 4
    acceptance = {
        "per_device_bytes_scaling": {
            f"{r['n_agent_shards']}x{r['n_model_shards']}":
                r["shard_bytes_measured"] for r in rows
            if r["impl"] == "dense"},
        "am_way_scaling_exact": all(
            r["shard_bytes_measured"]
            * r["n_agent_shards"] * r["n_model_shards"] == base_bytes
            for r in rows),
        "equivalence_checked_vs_flat": n_equiv_checked == len(rows)
        and bool(rows),
        "note": ("CPU host-platform devices: collectives run over loopback "
                 "memory, so wall-clock is not ICI-representative; the "
                 "transferable evidence is the exact 1/(A*M) per-device "
                 "byte scaling and the mesh2d_cost_model byte columns "
                 "(agent-axis gossip on D/M slices, model-axis loss "
                 "all-reduce), verified against the committed formulas by "
                 "check_regression.check_mesh2d_doc"),
    }
    out = {"workload": "fused H-step FedDec round, flat (n, D) buffer "
                       "sharded P('agents', 'model') on make_fed_mesh(A, M)",
           "backend": jax.default_backend(), "smoke": smoke,
           "devices": N_DEVICES, "rows": rows, "acceptance": acceptance}
    name = "BENCH_mesh2d.smoke.json" if smoke else "BENCH_mesh2d.json"
    path = os.path.join(common.ensure_results_dir(), name)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    common.write_csv("bench_mesh2d.csv", list(rows[0].keys()),
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
