"""Batched sweep engine vs per-seed Python loop — the lattice cost model.

The paper's figures are sweeps over (seed × H × topology); before the sweep
engine every figure script drove the flat engine once per run, paying a
full dispatch + host-sync round-trip per run per window while the device
idled between microscopic (n=20, D=25) kernels.  The sweep engine
(repro.core.sweep) stacks the whole lattice into one ``(R, n, D)`` buffer
and scans all runs in one compiled program.

This benchmark times, at the Fig. 4 workload shape (linreg n=20, d=25,
H=10, K=2, geographic graph, Laplacian weights, Theorem-1 stepsize):

  * ``loop``  — the per-seed baseline: one jitted single-run flat-engine
    H-step round per run per server window (compiled once, dispatched
    R·(T/H) times per trajectory with the state round-tripping through the
    host between windows) — exactly the pre-sweep figure-driver /
    train-loop pattern;
  * ``sweep`` — one batched call covering all R runs × T steps.

Both execute the identical T-step trajectories (each sweep slice is checked
against its single-run flat engine at 1e-5; observed exact), so
``loop_us / sweep_us`` is a pure throughput ratio at equal work.  Every row
carries the sweep cost model's exact columns
(``launch.analysis.sweep_cost_model``: state bytes, per-step streamed
bytes, dispatch counts) — pinned by CI's regression guard.

A second section measures the composed lowering — ``sweep_runs`` R ×
``mesh_agents`` s in ONE shard_map program
(repro.core.engine.make_sharded_sweep_round) — as weak scaling at 4
agents per shard (n = 4·s for s ∈ {1, 2, 4, 8}, R = 4) under 8 forced
host devices.  It runs in a child process (same isolation pattern as
bench_sharded) so the parent's jax device state is never touched; every
row's byte/dispatch columns are exact against
``launch.analysis.sharded_sweep_cost_model`` and every run slice is
checked against its single-run flat trajectory at 1e-5.

Emits the standard ``name,us_per_call,derived`` CSV lines plus
results/benchmarks/BENCH_sweep.json (smoke runs write
BENCH_sweep.smoke.json so the committed baseline is never clobbered).

Run:  PYTHONPATH=src python -m benchmarks.bench_sweep [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import feddec, flat as flat_lib, sweep, topology as topo
from repro.core.mixing import MixingDistribution
from repro.data import linreg
from repro.launch import analysis

N, D, M_ROWS, K = 20, 25, 10, 2  # fig4 shapes
FIG4_H = 10
N_DEVICES = 8
SHARDED_R = 4            # runs in the composed lattice
AGENTS_PER_SHARD = 4     # weak scaling: n = AGENTS_PER_SHARD * n_shards
_PART = "BENCH_sweep.sharded.part.json"  # child → parent handoff


def _setup(problem):
    graph = topo.geographic_graph(problem.n, 0.5, seed=1)
    fcfg = feddec.FedDecConfig(
        mixing=MixingDistribution(graph, scheme="laplacian"), h=FIG4_H, k=K)
    lr = common.paper_lr_fn(problem, FIG4_H)
    grad_fn = linreg.make_grad_fn(problem.m_rows)
    spec = flat_lib.make_flat_spec(jnp.zeros(problem.d))
    return fcfg, lr, grad_fn, spec


def bench_one(r_runs: int, t_steps: int, *, warmup: int, iters: int,
              check: bool) -> dict:
    problem = linreg.make_problem(n=N, m_rows=M_ROWS, d=D, seed=0)
    fcfg, lr, grad_fn, spec = _setup(problem)
    plan = sweep.make_sweep_plan([fcfg] * r_runs)

    # shared batch stream per step (the throughput comparison is about
    # execution, not data generation), per-run keys as in the figure scripts
    batches = jax.vmap(lambda k: linreg.sample_minibatch(problem, k, m=1))(
        jax.random.split(jax.random.key(3), t_steps))
    run_keys = jax.random.split(jax.random.key(42), r_runs)
    bat_sweep = jax.tree.map(
        lambda b: jnp.broadcast_to(b[:, None],
                                   (t_steps, r_runs) + b.shape[1:]), batches)

    # per-seed loop baseline: one compiled single-run H-step round,
    # dispatched per run per server window (batches pre-sliced outside the
    # timed region so the loop pays only dispatch + sync, as in bench_fused)
    assert t_steps % FIG4_H == 0, (t_steps, FIG4_H)
    win_batches = [
        jax.block_until_ready(jax.tree.map(
            lambda b: b[w * FIG4_H:(w + 1) * FIG4_H], batches))
        for w in range(t_steps // FIG4_H)]
    flat_round = flat_lib.make_flat_feddec_round(fcfg, spec, grad_fn, lr,
                                                 donate=False)
    state1 = flat_lib.init_flat_state(spec, jnp.zeros(D), N)

    def run_loop():
        outs = []
        for r in range(r_runs):
            st = state1
            for wb in win_batches:
                st, _ = flat_round(st, wb, run_keys[r])
            outs.append(st.flat)
        return outs

    sweep_round = sweep.make_sweep_feddec_round(plan, spec, grad_fn, lr,
                                                donate=False)
    state_r = sweep.init_sweep_state(plan, spec, jnp.zeros(D))

    def run_sweep():
        st, _ = sweep_round(state_r, bat_sweep, run_keys)
        return st.flat

    max_err = None
    if check:  # every sweep slice == its single-run flat trajectory
        ref = np.stack([np.asarray(o) for o in run_loop()])
        got = np.asarray(run_sweep())
        max_err = float(np.abs(got - ref).max())
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)

    us_loop = common.time_fn(run_loop, warmup=warmup, iters=iters)
    us_sweep = common.time_fn(run_sweep, warmup=warmup, iters=iters)
    model = analysis.sweep_cost_model(
        r_runs=r_runs, n_agents=N, d=spec.d, t_steps=t_steps, h=FIG4_H,
        param_bytes=4)
    speedup = us_loop / us_sweep
    steps_per_s = r_runs * t_steps / (us_sweep / 1e6)
    row = {"r_runs": r_runs, "n_agents": N, "d": spec.d,
           "t_steps": t_steps, "h": FIG4_H,
           "us_per_call": round(us_sweep, 1),
           "loop_us_per_call": round(us_loop, 1),
           "speedup": round(speedup, 2),
           "run_steps_per_s": round(steps_per_s, 1),
           "max_slice_err": max_err,
           "state_bytes": model["state_bytes"],
           "step_stream_bytes": model["step_stream_bytes"],
           "dispatches_loop": model["dispatches_loop"],
           "dispatches_sweep": model["dispatches_sweep"]}
    common.emit(f"sweep_R{r_runs}_T{t_steps}", us_sweep,
                f"loop_us={us_loop:.1f};speedup={speedup:.2f}x")
    return row


def _bench_sharded_sweep_child(smoke: bool) -> None:
    """Weak-scaling rows of the composed R runs × s shards lowering.

    Runs inside the forced-8-device child; writes the rows to the part
    file the parent merges into BENCH_sweep.json.
    """
    from repro.core import engine
    from repro.launch.mesh import make_agent_mesh

    assert len(jax.devices()) >= N_DEVICES, "forced host devices missing"
    if smoke:
        warmup, iters, t_steps = 1, 3, 30
        shard_grid = (1, 8)
    else:
        warmup, iters, t_steps = 2, 5, 200
        shard_grid = (1, 2, 4, 8)

    rows = []
    for n_shards in shard_grid:
        n = AGENTS_PER_SHARD * n_shards
        # c_base=1 keeps the label scale O(1) as n grows (the paper's
        # c_i = 2^i ramp reaches 2^32 at the widest row, which would make
        # the absolute 1e-5 slice check vacuous); constant stepsize under
        # the smoothness bound for the same reason — neither affects timing
        problem = linreg.make_problem(n=n, m_rows=M_ROWS, d=D, seed=0,
                                      c_base=1.0)
        graph = topo.ring_graph(n, k=1)
        fcfg = feddec.FedDecConfig(
            mixing=MixingDistribution(graph, scheme="laplacian"),
            h=FIG4_H, k=K)
        eta = jnp.asarray(0.5 / problem.l_smooth, jnp.float32)
        lr = lambda t: eta  # noqa: E731
        grad_fn = linreg.make_grad_fn(problem.m_rows)
        spec = flat_lib.make_flat_spec(jnp.zeros(problem.d))
        plan = sweep.make_sweep_plan([fcfg] * SHARDED_R)
        mesh = make_agent_mesh(n_shards)

        batches = jax.vmap(
            lambda k: linreg.sample_minibatch(problem, k, m=1))(
            jax.random.split(jax.random.key(3), t_steps))
        run_keys = jax.random.split(jax.random.key(42), SHARDED_R)
        bat_sweep = jax.tree.map(
            lambda b: jnp.broadcast_to(
                b[:, None], (t_steps, SHARDED_R) + b.shape[1:]), batches)

        round_fn = engine.make_sharded_sweep_round(plan, spec, grad_fn, lr,
                                                   mesh, donate=False)
        state0 = engine.shard_sweep_state(
            sweep.init_sweep_state(plan, spec, jnp.zeros(problem.d)), mesh)

        # every run slice == its single-run flat trajectory
        flat_round = flat_lib.make_flat_feddec_round(fcfg, spec, grad_fn,
                                                     lr, donate=False)
        out, _ = round_fn(state0, bat_sweep, run_keys)
        got = np.asarray(jax.device_get(out.flat))
        max_err = 0.0
        for r in range(SHARDED_R):
            s_ref, _ = flat_round(
                flat_lib.init_flat_state(spec, jnp.zeros(problem.d), n),
                batches, run_keys[r])
            err = float(np.abs(got[r] - np.asarray(s_ref.flat)).max())
            max_err = max(max_err, err)
            np.testing.assert_allclose(got[r], np.asarray(s_ref.flat),
                                       atol=1e-5, rtol=1e-5)

        us = common.time_fn(lambda: round_fn(state0, bat_sweep, run_keys),
                            warmup=warmup, iters=iters)
        from repro.core import sharded as sharded_lib
        cut = sharded_lib.cut_edge_stats(graph, n_shards)
        model = analysis.sharded_sweep_cost_model(
            r_runs=SHARDED_R, n_agents=n, d=spec.d, n_shards=n_shards,
            num_halo_rounds=cut["num_halo_rounds"], t_steps=t_steps,
            h=FIG4_H, param_bytes=4)
        run_steps_per_s = SHARDED_R * t_steps / (us / 1e6)
        rows.append({
            "r_runs": SHARDED_R, "n_agents": n, "n_shards": n_shards,
            "agents_per_shard": AGENTS_PER_SHARD, "d": spec.d,
            "t_steps": t_steps, "h": FIG4_H,
            "us_per_call": round(us, 1),
            "run_steps_per_s": round(run_steps_per_s, 1),
            "max_slice_err": max_err,
            "state_bytes_per_device": model["state_bytes_per_device"],
            "step_stream_bytes_per_device":
                model["step_stream_bytes_per_device"],
            "dense_collective_bytes": model["dense_collective_bytes"],
            "halo_collective_bytes": model["halo_collective_bytes"],
            "num_halo_rounds": model["num_halo_rounds"],
            "dispatches_loop": model["dispatches_loop"],
            "dispatches_sweep": model["dispatches_sweep"]})
        common.emit(f"sharded_sweep_R{SHARDED_R}_n{n}_s{n_shards}", us,
                    f"slice_err={max_err:.1e};"
                    f"halo_bytes={model['halo_collective_bytes']:.0f}")

    path = os.path.join(common.ensure_results_dir(), _PART)
    with open(path, "w") as f:
        json.dump({"sharded_rows": rows}, f)
    print(f"# wrote {path}")


def _run_sharded_sweep_section(smoke: bool) -> list[dict]:
    """Respawn into a forced-8-device child and collect its rows."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={N_DEVICES} "
                        + env.get("XLA_FLAGS", "")).strip()
    # a declared CPU simulation: the child never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")))
    cmd = [sys.executable, "-m", "benchmarks.bench_sweep", "--sharded-child"]
    if smoke:
        cmd.append("--smoke")
    res = subprocess.run(cmd, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    if res.returncode != 0:
        raise RuntimeError(f"bench_sweep sharded child failed "
                           f"({res.returncode})")
    path = os.path.join(common.ensure_results_dir(), _PART)
    with open(path) as f:
        rows = json.load(f)["sharded_rows"]
    os.remove(path)
    return rows


def main(smoke: bool = False) -> None:
    if smoke:
        warmup, iters, t_steps = 1, 3, 30
        grid = (4, 10)
    else:
        warmup, iters, t_steps = 2, 8, 200
        grid = (4, 10, 20, 40)

    rows = [bench_one(r, t_steps, warmup=warmup, iters=iters, check=True)
            for r in grid]
    sharded_rows = _run_sharded_sweep_section(smoke)

    fig4_row = next(r for r in rows if r["r_runs"] == 10)  # fig4's seed count
    acceptance = {
        "fig4_shape": {"n_agents": N, "d": D, "h": FIG4_H, "k": K,
                       "t_steps": t_steps, "seeds": 10},
        "speedup_at_fig4_seeds": fig4_row["speedup"],
        "best_speedup": max(r["speedup"] for r in rows),
        "equivalence_checked_vs_flat": True,
        "max_slice_err": max(r["max_slice_err"] for r in rows),
        "sharded_sweep": {
            "devices": N_DEVICES, "r_runs": SHARDED_R,
            "agents_per_shard": AGENTS_PER_SHARD,
            "max_slice_err": max(r["max_slice_err"] for r in sharded_rows),
            "equivalence_checked_vs_flat": True,
            "note": ("the composed lowering: R runs × s agent shards as "
                     "one shard_map program "
                     "(repro.core.engine.make_sharded_sweep_round).  Weak "
                     "scaling at 4 agents/shard: per-device state and "
                     "streamed bytes stay constant as agents are added "
                     "with devices "
                     "(analysis.sharded_sweep_cost_model columns)")},
        "note": ("loop = one jitted single-run flat H-step round "
                 "dispatched per run per server window (R·T/H dispatches "
                 "— the pre-sweep figure-driver / train-loop pattern); "
                 "sweep = one batched (R, n, D) program for the whole "
                 "lattice.  Identical trajectories (slices checked at "
                 "1e-5), so the ratio is pure throughput.  CPU CI "
                 "numbers; the dispatch-count and state/stream-byte "
                 "columns are the transferable evidence "
                 "(launch.analysis.sweep_cost_model)."),
    }
    out = {"workload": "FedDec linreg sweep lattice at fig4 shapes",
           "backend": jax.default_backend(), "smoke": smoke,
           "rows": rows, "sharded_rows": sharded_rows,
           "acceptance": acceptance}
    name = "BENCH_sweep.smoke.json" if smoke else "BENCH_sweep.json"
    path = os.path.join(common.ensure_results_dir(), name)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    common.write_csv("bench_sweep.csv", list(rows[0].keys()),
                     [tuple(r.values()) for r in rows])


if __name__ == "__main__":
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes / few iterations for CI")
    p.add_argument("--sharded-child", action="store_true",
                   help="internal: run the sharded-sweep section (assumes "
                        "the forced-device XLA flag is already set)")
    args = p.parse_args()
    if args.sharded_child:
        _bench_sharded_sweep_child(smoke=args.smoke)
    else:
        print("name,us_per_call,derived")
        main(smoke=args.smoke)
