"""Compressed-gossip subsystem benchmark (repro.core.compress).

Four sections, one JSON:

  1. **flat** — the whole-buffer EF gossip (encode → mix → diag-correct →
     residual) per compressor on one device: wall-clock plus the analytic
     per-row wire-payload bytes (`analysis.compress_row_bytes`).  The
     identity compressor is asserted bit-identical to the uncompressed mix.
  2. **halo** — the sharded engine's compressed ppermute halo
     (`sharded.make_sharded_ef_gossip`, 2/8 forced host devices): the
     encoded payload (int8 + scales / top-k values + indices / bf16) is
     what moves, so per-device collective bytes follow
     `analysis.compressed_halo_cost_model` — int8 ≈ 0.25× the f32 halo,
     the column CI's regression guard pins at ≤ 0.30.  Every timed config
     is first checked against the single-device EF gossip.
  3. **kernel** — the fused quantize→mix→dequantize Pallas kernels
     (kernels/compress_mix.py) vs the unfused XLA composition: off-TPU the
     kernels run in interpret mode, so the transferable evidence is the
     streamed-bytes model (fused receive side: q at 1 B/elem + p + y =
     9·nD vs the unfused 17·nD that materialises the f32 dequantized
     buffer), with correctness asserted against the XLA codec.
  4. **convergence** — the paper's linreg problem (fig4-style, fused flat
     rounds): int8+EF and bf16 must track the uncompressed trajectory
     (final running-mean loss within 5%); top-k trails but converges.

Emits the standard ``name,us_per_call,derived`` CSV lines plus
results/benchmarks/BENCH_compress.json (consumed by CI's perf-regression
guard and docs/PERFORMANCE.md).  Smoke runs write BENCH_compress.smoke.json
so the committed baseline is never clobbered.

Run:  PYTHONPATH=src python -m benchmarks.bench_compress [--smoke]

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

SCHEMES = ("none", "identity", "bf16", "int8", "topk:0.1")
HALO_SCHEMES = ("none", "bf16", "int8", "topk:0.1")


def kernel_stream_bytes(kind: str, n: int, d: int) -> float:
    """Analytic HBM bytes streamed per call by each kernel path
    (the column the regression guard re-derives):

      f32_mix             read x(4) + write y(4)                 =  8·nD
      fused_dequant_mix   read q(1) + read p(4) + write y(4)     =  9·nD
      xla_dequant_mix     dequant: read q(1) + write s(4);
                          mix: read s(4) + read p(4) + write y(4) = 17·nD
      fused_quant_mix     read u(4)+noise(4)+p(4), write y(4)+q(1) = 17·nD
                          (send side: the win is vs quantize + dequant +
                          mix as separate passes, not vs the receive side)
    """
    per_elem = {"f32_mix": 8.0, "fused_dequant_mix": 9.0,
                "xla_dequant_mix": 17.0, "fused_quant_mix": 17.0}[kind]
    return per_elem * n * d


def main(smoke: bool = False) -> None:
    """Respawn into a forced-8-device subprocess and stream its output."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={N_DEVICES} "
                        + env.get("XLA_FLAGS", "")).strip()
    # a declared CPU simulation: the child never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")))
    cmd = [sys.executable, "-m", "benchmarks.bench_compress", "--child"]
    if smoke:
        cmd.append("--smoke")
    res = subprocess.run(cmd, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    if res.returncode != 0:
        raise RuntimeError(f"bench_compress child failed ({res.returncode})")


def _child_main(smoke: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks import common
    from repro.core import compress as compress_lib
    from repro.core import flat as flat_lib
    from repro.core import sharded, theory, topology as topo
    from repro.core.feddec import FedDecConfig
    from repro.core.mixing import MixingDistribution
    from repro.data import linreg
    from repro.kernels import ops as kernel_ops
    from repro.launch import analysis
    from repro.launch.mesh import make_agent_mesh

    assert len(jax.devices()) >= N_DEVICES, "forced host devices missing"

    if smoke:
        warmup, iters = 1, 3
        d = 1 << 12
        d_kernel = 1 << 12
        t_conv = 160
    else:
        warmup, iters = 2, 5
        d = 1 << 16
        d_kernel = 1 << 15
        t_conv = 600
    n = 32

    graph = topo.ring_graph(n, k=2)
    md = MixingDistribution(graph, scheme="metropolis")
    w = jnp.asarray(md.sample(jax.random.key(0)))
    p_host = jax.random.normal(jax.random.key(1), (n, d), jnp.float32)
    res0 = jnp.zeros((n, d), jnp.float32)
    key_c = jax.random.key(7)

    def dense_mix(w, s):
        return jnp.einsum("ij,jd->id", w, s,
                          precision=jax.lax.Precision.HIGHEST)

    # -- 1. flat whole-buffer EF gossip ------------------------------------
    rows = []
    base_out = np.asarray(jax.jit(dense_mix)(w, p_host))
    flat_out = {}
    for scheme in SCHEMES:
        comp = compress_lib.parse_compress(scheme)
        if comp is None:
            fn = jax.jit(lambda w, p, r, k: (dense_mix(w, p), r))
        else:
            fn = jax.jit(compress_lib.make_flat_ef_gossip(comp, dense_mix, n))
        y, _ = fn(w, p_host, res0, key_c)
        flat_out[scheme] = np.asarray(y)
        us = common.time_fn(fn, w, p_host, res0, key_c,
                            warmup=warmup, iters=iters)
        row_bytes = analysis.compress_row_bytes(scheme, d, 4)
        rows.append({"section": "flat", "compress": scheme, "n_agents": n,
                     "d": d, "us_per_call": round(us, 1),
                     "row_payload_bytes": row_bytes})
        common.emit(f"compress_flat_{scheme}_n{n}_d{d}", us,
                    f"row_bytes={row_bytes:.0f}")
    np.testing.assert_array_equal(flat_out["identity"], base_out)
    np.testing.assert_array_equal(flat_out["none"], base_out)

    # -- 2. sharded compressed ppermute halo -------------------------------
    halo_rows = []
    for n_shards in (2, N_DEVICES):
        cut = sharded.cut_edge_stats(graph, n_shards)
        halo_model = analysis.compressed_halo_cost_model(
            n_agents=n, d=d, n_shards=n_shards,
            num_halo_rounds=cut["num_halo_rounds"], param_bytes=4,
            schemes=HALO_SCHEMES)
        mesh = make_agent_mesh(n_shards)
        p_sh = jax.device_put(p_host, NamedSharding(mesh, P("agents")))
        r_sh = jax.device_put(res0, NamedSharding(mesh, P("agents")))
        for scheme in HALO_SCHEMES:
            cfg = FedDecConfig(mixing=md, gossip_impl="sparse",
                               gossip_compress=scheme)
            fn = jax.jit(sharded.make_sharded_ef_gossip(cfg, mesh))
            y, _ = fn(w, p_sh, r_sh, key_c)
            np.testing.assert_allclose(np.asarray(y), flat_out[scheme],
                                       atol=1e-4, rtol=1e-4)
            us = common.time_fn(fn, w, p_sh, r_sh, key_c,
                                warmup=warmup, iters=iters)
            cm = halo_model[scheme]
            halo_rows.append({
                "section": "halo", "compress": scheme, "n_agents": n,
                "n_shards": n_shards, "d": d,
                "us_per_call": round(us, 1),
                "row_payload_bytes": cm["row_payload_bytes"],
                "collective_bytes": cm["collective_bytes"],
                "payload_ratio_vs_f32": cm["payload_ratio_vs_f32"],
                "num_halo_rounds": cut["num_halo_rounds"]})
            common.emit(
                f"compress_halo_{scheme}_n{n}_s{n_shards}", us,
                f"coll_bytes={cm['collective_bytes']:.0f};"
                f"ratio={cm['payload_ratio_vs_f32']:.3f}")

    # -- 3. fused Pallas kernels vs unfused XLA ----------------------------
    comp8 = compress_lib.parse_compress("int8")
    u = jax.random.normal(jax.random.key(2), (n, d_kernel), jnp.float32)
    p_k = jax.random.normal(jax.random.key(3), (n, d_kernel), jnp.float32)
    keys = jax.random.split(jax.random.key(4), n)
    scale = comp8.row_scale(u)
    noise = compress_lib._row_noise(keys, d_kernel)
    payload = comp8.encode(keys, u)
    q = payload["q"]

    def xla_dequant_mix(w, q, scale, p):
        s = q.astype(jnp.float32) * scale[:, None]
        return dense_mix(w, s) + jnp.diagonal(w)[:, None] * (p - s)

    kern_impls = {
        "f32_mix": (jax.jit(lambda: kernel_ops.gossip_mix(w, u)),),
        "fused_dequant_mix": (
            jax.jit(lambda: kernel_ops.dequant_mix(w, q, scale, p_k)),),
        "xla_dequant_mix": (
            jax.jit(lambda: xla_dequant_mix(w, q, scale, p_k)),),
        "fused_quant_mix": (
            jax.jit(lambda: kernel_ops.quant_mix(w, u, noise, p_k, scale)),),
    }
    # correctness: the receive-side fused kernel matches the XLA codec
    # composition; the fully-fused send side may flip borderline stochastic
    # roundings by one q-step (ulp differences under floor), so it is
    # checked to one step on a vanishing fraction of elements
    ref = np.asarray(kern_impls["xla_dequant_mix"][0]())
    np.testing.assert_allclose(
        np.asarray(kern_impls["fused_dequant_mix"][0]()), ref,
        atol=1e-4, rtol=1e-4)
    y_f, q_f = kern_impls["fused_quant_mix"][0]()
    dq = np.abs(np.asarray(q_f).astype(np.int32) -
                np.asarray(q).astype(np.int32))
    assert dq.max() <= 1 and (dq != 0).mean() < 1e-3, \
        (dq.max(), (dq != 0).mean())
    np.testing.assert_allclose(np.asarray(y_f), ref, atol=0.1)

    kernel_rows = []
    for name, (fn,) in kern_impls.items():
        us = common.time_fn(fn, warmup=warmup, iters=iters)
        mb = kernel_stream_bytes(name, n, d_kernel)
        kernel_rows.append({
            "section": "kernel", "impl": name, "n_agents": n, "d": d_kernel,
            "us_per_call": round(us, 1), "model_stream_bytes": mb,
            "interpret_mode": name.startswith("fused")
            and not kernel_ops.on_tpu()})
        common.emit(f"compress_kernel_{name}_n{n}_d{d_kernel}", us,
                    f"model_bytes={mb:.0f}")

    # -- 4. fig4-style linreg convergence ----------------------------------
    problem = linreg.make_problem(n=8, seed=0, c_base=1.3)
    g_small = topo.geographic_graph(problem.n, 0.6, seed=3)
    md_small = MixingDistribution(g_small, scheme="laplacian")
    h = 10
    lr = theory.paper_stepsize(
        problem.mu, theory.gamma(problem.l_smooth, problem.mu, h))
    grad_fn = linreg.make_grad_fn(problem.m_rows)
    spec = flat_lib.make_flat_spec(jnp.zeros(problem.d))
    keys_b = jax.random.split(jax.random.key(11), t_conv)
    batches = jax.vmap(lambda k: linreg.sample_minibatch(problem, k, m=1))(
        keys_b)
    conv_rows = []
    final_loss = {}
    for scheme in ("none", "bf16", "int8", "topk:0.25"):
        cfg = FedDecConfig(mixing=md_small, h=h, k=2, gossip_impl="dense",
                           gossip_compress=scheme)
        round_fn = flat_lib.make_flat_feddec_round(
            cfg, spec, grad_fn, lr, donate=False,
            metrics_fn=lambda s: {
                "subopt": problem.suboptimality(spec.unflatten(s.flat))})
        state = flat_lib.init_flat_state(spec, jnp.zeros(problem.d),
                                         problem.n, compress=scheme)
        state, metrics = round_fn(state, batches, jax.random.key(5))
        losses = np.asarray(metrics["loss"])
        subopt = np.asarray(metrics["subopt"])
        tail = max(1, t_conv // 10)
        final_loss[scheme] = float(losses[-tail:].mean())
        conv_rows.append({
            "section": "convergence", "compress": scheme,
            "t_steps": t_conv, "h": h,
            "final_loss_tail_mean": final_loss[scheme],
            "final_subopt_tail_mean": float(subopt[-tail:].mean()),
            "loss_curve_sampled": [round(float(x), 6)
                                   for x in losses[::max(1, t_conv // 40)]]})
        common.emit(f"compress_linreg_{scheme}_t{t_conv}", 0.1,
                    f"final_loss={final_loss[scheme]:.6f}")

    int8_ratio = final_loss["int8"] / final_loss["none"]
    bf16_ratio = final_loss["bf16"] / final_loss["none"]
    big = [r for r in halo_rows if r["n_shards"] == N_DEVICES]

    def coll(scheme):
        return next(r["collective_bytes"] for r in big
                    if r["compress"] == scheme)

    acceptance = {
        "identity_bit_identical_to_uncompressed": True,
        "equivalence_checked_sharded_vs_flat": True,
        "int8_halo_ratio_vs_f32": round(coll("int8") / coll("none"), 4),
        "int8_halo_ratio_ok": coll("int8") / coll("none") <= 0.30,
        "kernel_fused_vs_unfused_model_bytes": round(
            kernel_stream_bytes("fused_dequant_mix", n, d_kernel)
            / kernel_stream_bytes("xla_dequant_mix", n, d_kernel), 3),
        "int8_final_loss_ratio": round(int8_ratio, 4),
        "bf16_final_loss_ratio": round(bf16_ratio, 4),
        "int8_tracks_uncompressed_within_5pct":
            bool(abs(int8_ratio - 1.0) <= 0.05),
        "note": ("CPU host devices: halo collectives run over loopback and "
                 "Pallas kernels in interpret mode, so wall-clock is not "
                 "ICI/TPU-representative; the transferable evidence is the "
                 "exact collective_bytes / row_payload_bytes / "
                 "model_stream_bytes columns "
                 "(analysis.compress_row_bytes & compressed_halo_cost_model "
                 "at TPU constants) plus the s8 ppermute payloads visible "
                 "in the compiled HLO (tests/test_compress.py)"),
    }
    out = {"workload": "compressed gossip: EF codecs on the flat buffer, "
                       "compressed ppermute halo on the sharded engine, "
                       "fused quant/dequant-mix Pallas kernels, linreg "
                       "convergence",
           "backend": jax.default_backend(), "smoke": smoke,
           "devices": N_DEVICES,
           "rows": rows + halo_rows + kernel_rows,
           "convergence_rows": conv_rows,
           "acceptance": acceptance}
    name = "BENCH_compress.smoke.json" if smoke else "BENCH_compress.json"
    path = os.path.join(common.ensure_results_dir(), name)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    common.write_csv(
        "bench_compress.csv",
        ["section", "compress_or_impl", "n_agents", "n_shards", "d",
         "us_per_call", "bytes_column"],
        [(r["section"], r.get("compress", r.get("impl")), r["n_agents"],
          r.get("n_shards", 1), r["d"], r["us_per_call"],
          r.get("collective_bytes",
                r.get("model_stream_bytes", r.get("row_payload_bytes"))))
         for r in rows + halo_rows + kernel_rows])


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
