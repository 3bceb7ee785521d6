"""Fused-round benchmark: one-pass update+gossip vs the two-pass body.

Three sections, one JSON:

  1. **engine** — the real flat executor at the fig4 linreg shape, fused
     (``fuse_update_mix=True`` → kernels/update_mix.py) vs unfused, across
     gossip impls × sgd/momentum × codec on/off.  Every fused trajectory is
     asserted against its unfused twin (final buffer within 1e-5) before it
     is timed, so the wall-clock columns always describe equivalent math.
  2. **headline** — the buffer-pass evidence at n=1024, D=2^20 (the 4 GiB
     flat buffer): the unfused body dispatches update and mix separately,
     materialising the post-update buffer p between them; the fused body
     is the same math in one dispatch, so p never round-trips through HBM.
     Off-TPU the Pallas kernels interpret (far too slow at 2^30 elements),
     so both sides run the identical XLA sparse-ELL composition and only
     the dispatch split differs — exactly the pass delta
     ``analysis.roundfuse_cost_model`` counts (sgd 5→3 passes, momentum
     7→5), which is what the regression guard pins, exact.
  3. **sharded** — the boundary/interior overlapped halo (8 forced host
     devices): ``sharded.boundary_row_split`` row counts, the cost model's
     halo_payload_ratio / predicted_overlap_fraction, measured round
     wall-clock, and a final-buffer check against the unsharded flat round.

Emits the standard ``name,us_per_call,derived`` CSV lines plus
results/benchmarks/BENCH_roundfuse.json (consumed by CI's perf-regression
guard and docs/PERFORMANCE.md).  Smoke runs write
BENCH_roundfuse.smoke.json so the committed baseline is never clobbered.

Run:  PYTHONPATH=src python -m benchmarks.bench_roundfuse [--smoke]

Re-executes itself in a forced-8-device subprocess (same isolation pattern
as bench_sharded.py).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

N_DEVICES = 8
HEADLINE_N = 1024
HEADLINE_D = 1 << 20


def main(smoke: bool = False) -> None:
    """Respawn into a forced-8-device subprocess and stream its output."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={N_DEVICES} "
                        + env.get("XLA_FLAGS", "")).strip()
    # a declared CPU simulation: the child never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")))
    cmd = [sys.executable, "-m", "benchmarks.bench_roundfuse", "--child"]
    if smoke:
        cmd.append("--smoke")
    res = subprocess.run(cmd, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    if res.returncode != 0:
        raise RuntimeError(f"bench_roundfuse child failed ({res.returncode})")


def _child_main(smoke: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import common
    from repro.core import flat as flat_lib
    from repro.core import sharded, theory, topology as topo
    from repro.core.feddec import FedDecConfig
    from repro.core.mixing import MixingDistribution
    from repro.data import linreg
    from repro.launch import analysis
    from repro.launch.mesh import make_agent_mesh
    from repro.optim import optimizers as optim

    assert len(jax.devices()) >= N_DEVICES, "forced host devices missing"

    # t_engine stays at 40 in both modes: the fused-vs-unfused 1e-5 window
    # — like every trajectory-equivalence gate in this repo — is a short
    # horizon; past ~100 linreg steps the (equivalent) fusion-level float
    # noise is chaotically amplified and the comparison stops meaning
    # anything.  Full runs scale the *shapes*, not the horizon.
    t_engine = 40
    if smoke:
        warmup, iters = 1, 3
        head_n, head_d = 128, 1 << 14
        shard_d, shard_h = 1 << 10, 4
    else:
        warmup, iters = 1, 3  # the headline rows stream 4 GiB buffers
        head_n, head_d = HEADLINE_N, HEADLINE_D
        shard_d, shard_h = 1 << 12, 8

    def cost_cols(n, d, optimizer, codec):
        cm = analysis.roundfuse_cost_model(
            n_agents=n, d=d, optimizer=optimizer, codec=codec, param_bytes=4)
        return {k: cm[k] for k in ("passes_unfused", "passes_fused",
                                   "unfused_pass_bytes", "fused_pass_bytes",
                                   "pass_ratio")}

    # -- 1. engine: real fused executor at the fig4 shape ------------------
    problem = linreg.make_problem(n=8, seed=0, c_base=1.3)
    g_small = topo.geographic_graph(problem.n, 0.6, seed=3)
    md_small = MixingDistribution(g_small, scheme="laplacian")
    h = 10
    lr = theory.paper_stepsize(
        problem.mu, theory.gamma(problem.l_smooth, problem.mu, h))
    grad_fn = linreg.make_grad_fn(problem.m_rows)
    spec = flat_lib.make_flat_spec(jnp.zeros(problem.d))
    keys_b = jax.random.split(jax.random.key(11), t_engine)
    batches = jax.vmap(lambda k: linreg.sample_minibatch(problem, k, m=1))(
        keys_b)

    engine_grid = [("dense", "sgd", "none"), ("dense", "momentum", "none"),
                   ("sparse", "sgd", "none"), ("sparse", "momentum", "none"),
                   ("pallas", "sgd", "none"), ("dense", "sgd", "int8"),
                   ("sparse", "sgd", "int8")]
    rows = []
    max_err_engine = 0.0
    for impl, opt_name, codec in engine_grid:
        cfg = FedDecConfig(mixing=md_small, h=h, k=2, gossip_impl=impl,
                           gossip_compress=codec)
        opt = optim.sgd() if opt_name == "sgd" else optim.momentum_sgd(0.9)
        finals = {}
        timed = {}
        for fused in (False, True):
            round_fn = flat_lib.make_flat_feddec_round(
                cfg, spec, grad_fn, lr, optimizer=opt, donate=False,
                fuse_update_mix=fused)
            state = flat_lib.init_flat_state(
                spec, jnp.zeros(problem.d), problem.n, optimizer=opt,
                compress=codec)
            out, _ = round_fn(state, batches, jax.random.key(5))
            finals[fused] = np.asarray(out.flat)
            timed[fused] = common.time_fn(
                round_fn, state, batches, jax.random.key(5),
                warmup=warmup, iters=iters)
        err = float(np.abs(finals[True] - finals[False]).max())
        np.testing.assert_allclose(finals[True], finals[False], atol=1e-5)
        max_err_engine = max(max_err_engine, err)
        row = {"section": "engine", "impl": impl, "optimizer": opt_name,
               "codec": codec != "none", "n_agents": problem.n,
               "d": problem.d, "t_steps": t_engine,
               "us_fused": round(timed[True], 1),
               "us_unfused": round(timed[False], 1),
               "speedup": round(timed[False] / timed[True], 3),
               "max_abs_err": err,
               **cost_cols(problem.n, problem.d, opt_name, codec != "none")}
        rows.append(row)
        common.emit(f"roundfuse_engine_{impl}_{opt_name}_{codec}",
                    timed[True],
                    f"speedup={row['speedup']};ratio={row['pass_ratio']:.3f}")

    # -- 2. headline: buffer-pass split at n=1024, D=2^20 ------------------
    graph = topo.ring_graph(head_n, k=2)
    md = MixingDistribution(graph, scheme="metropolis")
    w = jnp.asarray(md.sample(jax.random.key(0)))
    adj = np.asarray(graph.adjacency)
    max_deg = int(adj.sum(axis=1).max()) + 1  # neighbours + self
    nbr = np.zeros((head_n, max_deg), np.int32)
    for i in range(head_n):
        cols = [i] + list(np.flatnonzero(adj[i]))
        nbr[i, :len(cols)] = cols
        nbr[i, len(cols):] = i  # duplicates get zero weight below
    nbr_j = jnp.asarray(nbr)

    def ell_weights(w):
        wg = jnp.take_along_axis(w, nbr_j, axis=1)              # (n, deg)
        first = jnp.argmax(nbr_j[:, :, None] == nbr_j[:, None, :], axis=1)
        return jnp.where(first == jnp.arange(max_deg)[None], wg, 0.0)

    def ell_mix(w, p):
        wg = ell_weights(w)
        y = jnp.zeros_like(p)
        for j in range(max_deg):  # one (n, D) stream per neighbour slot
            y = y + wg[:, j, None] * jnp.take(p, nbr_j[:, j], axis=0)
        return y

    def update(x, g, eta, m=None):
        if m is None:
            return x - eta * g
        new_m = 0.9 * m + g
        return x - eta * new_m, new_m

    x = jax.random.normal(jax.random.key(1), (head_n, head_d), jnp.float32)
    g = jax.random.normal(jax.random.key(2), (head_n, head_d), jnp.float32)
    m0 = jnp.zeros_like(x)
    eta = jnp.float32(0.05)
    upd_sgd = jax.jit(update)
    upd_mom = jax.jit(update)
    mix = jax.jit(ell_mix)
    fused_sgd = jax.jit(lambda w, x, g, eta: ell_mix(w, update(x, g, eta)))

    def fused_mom_body(w, x, g, eta, m):
        p, new_m = update(x, g, eta, m)
        return ell_mix(w, p), new_m

    fused_mom = jax.jit(fused_mom_body)

    for opt_name in ("sgd", "momentum"):
        if opt_name == "sgd":
            def unfused_call():
                return mix(w, upd_sgd(x, g, eta))

            def fused_call():
                return fused_sgd(w, x, g, eta)
        else:
            def unfused_call():
                p, new_m = upd_mom(x, g, eta, m0)
                return mix(w, p), new_m

            def fused_call():
                return fused_mom(w, x, g, eta, m0)
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(fused_call())[0]),
            np.asarray(jax.tree.leaves(unfused_call())[0]), atol=1e-5)
        us_un = common.time_fn(unfused_call, warmup=warmup, iters=iters)
        us_f = common.time_fn(fused_call, warmup=warmup, iters=iters)
        row = {"section": "headline", "impl": "sparse",
               "optimizer": opt_name, "codec": False, "n_agents": head_n,
               "d": head_d, "t_steps": 1,
               "us_fused": round(us_f, 1), "us_unfused": round(us_un, 1),
               "speedup": round(us_un / us_f, 3), "max_abs_err": 0.0,
               **cost_cols(head_n, head_d, opt_name, False)}
        rows.append(row)
        common.emit(f"roundfuse_headline_{opt_name}_n{head_n}_d{head_d}",
                    us_f,
                    f"speedup={row['speedup']};ratio={row['pass_ratio']:.3f}")
    del x, g, m0

    # -- 3. sharded: boundary/interior overlapped halo ---------------------
    n_sh, d_sh = 64, shard_d
    graph_sh = topo.ring_graph(n_sh, k=2)
    md_sh = MixingDistribution(graph_sh, scheme="metropolis")
    spec_sh = flat_lib.make_flat_spec(jnp.zeros(d_sh))

    def quad_grad(p, batch, key):
        del key
        return 0.5 * jnp.sum((p - batch) ** 2), p - batch

    def const_lr(t):
        return jnp.asarray(0.05, jnp.float32)

    batches_sh = jax.random.normal(jax.random.key(3), (shard_h, n_sh, d_sh),
                                   jnp.float32)
    key_sh = jax.random.key(4)
    cfg_sh = FedDecConfig(mixing=md_sh, h=shard_h, k=2, gossip_impl="sparse")
    flat_round = flat_lib.make_flat_feddec_round(
        cfg_sh, spec_sh, quad_grad, const_lr, donate=False)
    ref_state, _ = flat_round(
        flat_lib.init_flat_state(spec_sh, jnp.zeros(d_sh), n_sh),
        batches_sh, key_sh)
    ref_flat = np.asarray(ref_state.flat)

    sharded_rows = []
    for n_shards in (2, N_DEVICES):
        mesh = make_agent_mesh(n_shards)
        round_fn = sharded.make_sharded_feddec_round(
            cfg_sh, spec_sh, quad_grad, const_lr, mesh, donate=False)
        state = sharded.shard_flat_state(
            flat_lib.init_flat_state(spec_sh, jnp.zeros(d_sh), n_sh), mesh)
        out, _ = round_fn(state, batches_sh, key_sh)
        err = float(np.abs(np.asarray(out.flat) - ref_flat).max())
        np.testing.assert_allclose(np.asarray(out.flat), ref_flat, atol=1e-5)
        us = common.time_fn(lambda: round_fn(state, batches_sh, key_sh),
                            warmup=warmup, iters=iters)
        split = sharded.boundary_row_split(graph_sh, n_shards)
        cut = sharded.cut_edge_stats(graph_sh, n_shards)
        cm = analysis.roundfuse_cost_model(
            n_agents=n_sh, d=d_sh, optimizer="sgd", codec=False,
            param_bytes=4, n_shards=n_shards,
            boundary_rows_per_shard=split["b_max"],
            num_halo_rounds=cut["num_halo_rounds"])
        row = {"section": "sharded", "n_agents": n_sh, "n_shards": n_shards,
               "d": d_sh, "h": shard_h, "us_per_round": round(us, 1),
               "max_abs_err": err,
               "boundary_rows_per_shard": cm["boundary_rows_per_shard"],
               "interior_rows_per_shard": cm["interior_rows_per_shard"],
               "num_halo_rounds": cm["num_halo_rounds"],
               "halo_bytes_full": cm["halo_bytes_full"],
               "halo_bytes_boundary": cm["halo_bytes_boundary"],
               "halo_payload_ratio": cm["halo_payload_ratio"],
               "predicted_overlap_fraction": cm["predicted_overlap_fraction"]}
        sharded_rows.append(row)
        common.emit(
            f"roundfuse_sharded_n{n_sh}_s{n_shards}", us,
            f"halo_ratio={cm['halo_payload_ratio']:.3f};"
            f"overlap={cm['predicted_overlap_fraction']:.3f}")

    head = [r for r in rows if r["section"] == "headline"]
    acceptance = {
        "equivalence_checked_fused_vs_unfused": True,
        "max_abs_err_engine": max_err_engine,
        "sgd_pass_ratio": next(r["pass_ratio"] for r in rows
                               if r["optimizer"] == "sgd"
                               and not r["codec"]),
        "headline_speedup_sgd": next(r["speedup"] for r in head
                                     if r["optimizer"] == "sgd"),
        "headline_speedup_momentum": next(r["speedup"] for r in head
                                          if r["optimizer"] == "momentum"),
        "sharded_max_abs_err": max(r["max_abs_err"] for r in sharded_rows),
        "note": ("CPU: the engine rows time the real fused executor (Pallas "
                 "in interpret mode at the tiny fig4 D); the headline rows "
                 "time the identical XLA sparse-ELL math with the dispatch "
                 "split as the only variable, because interpret mode cannot "
                 "stream 2^30 elements — the transferable evidence is the "
                 "exact passes_/pass_bytes columns "
                 "(analysis.roundfuse_cost_model) plus the measured "
                 "one-dispatch-vs-two speedup at the 4 GiB buffer"),
    }
    out = {"workload": "fused update+gossip round: one pass over the flat "
                       "(n, D) buffer vs the unfused two-pass body, plus "
                       "the sharded boundary-halo/interior-compute overlap",
           "backend": jax.default_backend(), "smoke": smoke,
           "devices": N_DEVICES,
           "rows": rows, "sharded_rows": sharded_rows,
           "acceptance": acceptance}
    name = "BENCH_roundfuse.smoke.json" if smoke else "BENCH_roundfuse.json"
    path = os.path.join(common.ensure_results_dir(), name)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    common.write_csv(
        "bench_roundfuse.csv",
        ["section", "impl_or_shards", "optimizer", "codec", "n_agents", "d",
         "us_fused", "us_unfused", "speedup", "pass_ratio"],
        [(r["section"], r["impl"], r["optimizer"], r["codec"], r["n_agents"],
          r["d"], r["us_fused"], r["us_unfused"], r["speedup"],
          r["pass_ratio"]) for r in rows]
        + [(r["section"], r["n_shards"], "sgd", False, r["n_agents"], r["d"],
            r["us_per_round"], "", "", r["halo_payload_ratio"])
           for r in sharded_rows])


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
