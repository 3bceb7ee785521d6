import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape).

The two lines above MUST stay first — jax locks the device count at first
initialisation, and the production meshes need 512 placeholder host devices.
Do NOT import this module from tests (they must keep seeing 1 device); run
it as ``PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
[--mesh single|multi|both]``.

For every combination this script:
  1. builds the step (FedDec train / prefill / decode) and its
     ShapeDtypeStruct inputs — no arrays are ever materialised;
  2. jits with explicit in_shardings on the production mesh and runs
     ``.lower().compile()`` — sharding mismatches, unsupported collectives
     or compile-time OOMs fail loudly here;
  3. records ``compiled.memory_analysis()`` (does it fit HBM?),
     ``cost_analysis()`` (FLOPs/bytes) and the collective-byte breakdown
     parsed from the optimized HLO, as JSON under results/dryrun/.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.configs import ARCH_NAMES, SHAPES, get_config
from repro.launch import analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_lowerable

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train, 2·N_active·D per decoded token."""
    n_active = cfg.num_active_params()
    if shape.kind == "train":
        return 6.0 * n_active * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.seq_len * shape.global_batch
    return 2.0 * n_active * 1 * shape.global_batch  # one token per request


def _gossip_model(cfg, axes, state_layout: str,
                  mesh_agents: int | None = None,
                  mesh_model: int | None = None) -> dict:
    """Analytic per-impl gossip cost for this (arch × mesh) — the flat-path
    extension of the roofline: predicted per-step mix time for the tree
    leaf-wise dense path vs the flat dense/pallas/sparse whole-buffer ops,
    plus the compressed-payload byte model (per-row wire bytes for every
    gossip_compress scheme; repro.core.compress).

    ``mesh_agents=N`` adds the agent-sharded engine's model (per-device
    bytes + collective bytes on the graph's cut edges — the psum_scatter
    vs ppermute-halo comparison of repro.core.sharded) and the compressed
    halo collective bytes per scheme.  ``mesh_model=M`` with
    ``mesh_agents=A`` additionally records the 2-D (A, M) mesh byte model
    (analysis.mesh2d_cost_model): n/A · D/M state per device, agent-axis
    gossip on D/M-wide slices, model-axis matmul/loss collectives."""
    from repro.core import sharded as sharded_lib
    from repro.launch.steps import adapt_for_mesh, build_fed_setup
    from repro.models import build_model
    acfg = adapt_for_mesh(cfg, axes)
    fcfg, n_agents = build_fed_setup(acfg, axes)
    params = jax.eval_shape(build_model(acfg).init, jax.random.key(0))
    leaves = jax.tree.leaves(params)
    d = int(sum(int(np.prod(l.shape)) for l in leaves))
    pbytes = jnp.dtype(leaves[0].dtype).itemsize
    model = analysis.gossip_cost_model(
        n_agents=n_agents, d=d, num_leaves=len(leaves),
        num_directed_edges=2 * fcfg.mixing.graph.num_edges,
        param_bytes=pbytes)
    rec = {"n_agents": n_agents, "d": d, "num_leaves": len(leaves),
           "param_bytes": int(pbytes),
           "state_layout": state_layout, "impls": model,
           "compress_payload_bytes_per_row": {
               scheme: analysis.compress_row_bytes(scheme, d, pbytes)
               for scheme in analysis.COMPRESS_SCHEMES}}
    if mesh_agents:
        if n_agents % mesh_agents:
            rec["sharded"] = {"skipped": f"mesh_agents={mesh_agents} does "
                              f"not divide n_agents={n_agents}"}
        else:
            cut = sharded_lib.cut_edge_stats(fcfg.mixing.graph, mesh_agents)
            split = sharded_lib.boundary_row_split(fcfg.mixing.graph,
                                                   mesh_agents)
            rec["sharded"] = {
                **cut,
                "boundary_rows_max": split["b_max"],
                "interior_rows_min": split["interior_min"],
                # the halo/compute overlap window of the boundary-sliced
                # exchange (core/sharded.py halo mixers)
                "roundfuse": analysis.roundfuse_cost_model(
                    n_agents=n_agents, d=d, n_shards=mesh_agents,
                    boundary_rows_per_shard=split["b_max"],
                    num_halo_rounds=cut["num_halo_rounds"],
                    param_bytes=pbytes),
                "impls": analysis.sharded_gossip_cost_model(
                    n_agents=n_agents, d=d, n_shards=mesh_agents,
                    num_cut_edges=cut["num_cut_edges"],
                    num_halo_rounds=cut["num_halo_rounds"],
                    param_bytes=pbytes),
                "compress": analysis.compressed_halo_cost_model(
                    n_agents=n_agents, d=d, n_shards=mesh_agents,
                    num_halo_rounds=cut["num_halo_rounds"],
                    param_bytes=pbytes)}
            if mesh_model and mesh_model > 1:
                if d % mesh_model:
                    rec["mesh2d"] = {"skipped": f"mesh_model={mesh_model} "
                                     f"does not divide d={d}"}
                else:
                    rec["mesh2d"] = {
                        "n_agent_shards": mesh_agents,
                        "n_model_shards": mesh_model,
                        "impls": analysis.mesh2d_cost_model(
                            n_agents=n_agents, d=d,
                            n_agent_shards=mesh_agents,
                            n_model_shards=mesh_model,
                            num_halo_rounds=cut["num_halo_rounds"],
                            param_bytes=pbytes)}
    return rec


def run_one(arch: str, shape_name: str, multi_pod: bool,
            out_dir: str | None = RESULTS_DIR,
            fused_steps: int | None = None,
            state_layout: str = "tree",
            mesh_agents: int | None = None,
            mesh_model: int | None = None,
            gossip_compress: str = "none",
            sweep_runs: int | None = None,
            sweep_axis: str = "seed",
            n_total: int | None = None,
            cohort_size: int = 256,
            sampling: str = "uniform",
            staleness: float = 0.0,
            fuse_update_mix: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = shd.axes_for_mesh(mesh)
    chips = mesh.devices.size
    tag = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}"
    if fused_steps and shape.kind == "train":
        tag += f"__fused{fused_steps}"
    if state_layout in ("flat", "sharded") and shape.kind == "train":
        tag += f"__{state_layout}"
        if state_layout == "sharded" and mesh_model and mesh_model > 1:
            tag += f"__m{mesh_model}"
    if fuse_update_mix and shape.kind == "train":
        tag += "__updmix"
    if sweep_runs and shape.kind == "train":
        tag += f"__sweep{sweep_runs}-{sweep_axis}"
    if n_total and shape.kind == "train":
        tag += f"__pop{n_total}"
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
                 "fused_steps": fused_steps if shape.kind == "train" else None,
                 "state_layout": state_layout
                 if shape.kind == "train" else None}
    if gossip_compress != "none" and shape.kind == "train":
        rec["gossip_compress"] = gossip_compress
    if sweep_runs and shape.kind == "train":
        rec["sweep_runs"] = sweep_runs
        rec["sweep_axis"] = sweep_axis
    if n_total and shape.kind == "train":
        rec["population"] = {"n_total": n_total, "cohort_size": cohort_size,
                             "sampling": sampling, "staleness": staleness}
    t0 = time.time()
    try:
        from repro.configs.base import FedConfig
        fed = FedConfig(gossip_compress=gossip_compress) \
            if gossip_compress != "none" else None
        low = build_lowerable(cfg, shape, axes, fed=fed,
                              fused_steps=fused_steps,
                              state_layout=state_layout, mesh=mesh,
                              mesh_model=mesh_model,
                              sweep_runs=sweep_runs
                              if shape.kind == "train" else None,
                              sweep_axis=sweep_axis,
                              fuse_update_mix=fuse_update_mix
                              and shape.kind == "train")
        lowered = low.lower(mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = analysis.hlo_analysis.analyze_hlo(compiled.as_text())
        steps_per_call = (fused_steps if fused_steps
                          and shape.kind == "train" else 1)
        report = analysis.roofline_terms(
            name=tag, chips=chips, per_device_flops=hlo.flops,
            per_device_bytes=hlo.traffic_bytes,
            collective_bytes=hlo.collective_bytes,
            model_flops=_model_flops(cfg, shape) * steps_per_call)

        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "peak_bytes": getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0),
            },
            # raw cost_analysis kept for reference; it does NOT weight loop
            # trip counts (see hlo_analysis docstring) — roofline uses the
            # loop-aware numbers
            "cost_analysis_raw": {
                "flops_per_device": float(cost.get("flops", 0.0)),
                "bytes_per_device": float(cost.get("bytes accessed", 0.0))},
            "hlo": {"flops_per_device": hlo.flops,
                    "traffic_bytes_per_device": hlo.traffic_bytes,
                    "collective_bytes": hlo.collective_bytes,
                    "collective_counts": hlo.collective_counts,
                    "collective_bytes_by_kind": hlo.collective_bytes_by_kind},
            "roofline": report.row(),
        })
        if shape.kind == "train":
            rec["gossip_cost_model"] = _gossip_model(cfg, axes, state_layout,
                                                     mesh_agents, mesh_model)
            if state_layout == "flat":
                gm = rec["gossip_cost_model"]
                # buffer-pass bytes of the fused vs unfused round body
                rec["roundfuse_cost_model"] = analysis.roundfuse_cost_model(
                    n_agents=gm["n_agents"], d=gm["d"], optimizer="sgd",
                    codec=gossip_compress != "none",
                    param_bytes=gm["param_bytes"])
            if sweep_runs:
                gm = rec["gossip_cost_model"]
                rec["sweep_cost_model"] = analysis.sweep_cost_model(
                    r_runs=sweep_runs, n_agents=gm["n_agents"], d=gm["d"],
                    param_bytes=gm["param_bytes"],
                    residual=gossip_compress != "none")
                sh = gm.get("sharded", {})
                if mesh_agents and "num_halo_rounds" in sh:
                    # the composed R runs × s shards lowering
                    rec["sharded_sweep_cost_model"] = \
                        analysis.sharded_sweep_cost_model(
                            r_runs=sweep_runs, n_agents=gm["n_agents"],
                            d=gm["d"], n_shards=mesh_agents,
                            num_halo_rounds=sh["num_halo_rounds"],
                            param_bytes=gm["param_bytes"],
                            residual=gossip_compress != "none")
            if n_total:
                gm = rec["gossip_cost_model"]
                rec["population_cost_model"] = analysis.population_cost_model(
                    n_total=n_total, cohort_size=cohort_size, d=gm["d"],
                    max_degree=8, h=fused_steps or 1,
                    param_bytes=gm["param_bytes"])
        print(f"[ok]   {tag}: lower {t_lower:.0f}s compile {t_compile:.0f}s")
        print(f"       memory_analysis: {mem}")
        print(f"       hlo(loop-aware): {hlo.summary()}")
        print(f"       roofline: compute {report.compute_s * 1e3:.2f}ms "
              f"memory {report.memory_s * 1e3:.2f}ms collective "
              f"{report.collective_s * 1e3:.2f}ms → {report.dominant}; "
              f"useful-flops ratio {report.useful_flops_ratio:.2f}")
        if shape.kind == "train" and state_layout == "flat":
            gm = rec["gossip_cost_model"]
            pred = ", ".join(
                f"{k} {v['pred_us']:.0f}µs" for k, v in gm["impls"].items())
            print(f"       gossip/step (n={gm['n_agents']}, "
                  f"D={gm['d']:.2e}, {gm['num_leaves']} leaves): {pred}")
            rf = rec["roundfuse_cost_model"]
            print(f"       fused round: {rf['passes_unfused']}→"
                  f"{rf['passes_fused']} buffer passes/step "
                  f"({rf['pass_ratio']:.2f}x bytes)"
                  + (" [--fuse-update-mix compiled]"
                     if fuse_update_mix else ""))
        if shape.kind == "train" and sweep_runs:
            sm = rec["sweep_cost_model"]
            print(f"       sweep lattice R={sweep_runs} ({sweep_axis}): "
                  f"state {sm['state_bytes'] / 1e9:.2f} GB "
                  f"(R× flat buffer), step stream "
                  f"{sm['step_stream_bytes'] / 1e9:.2f} GB, "
                  f"1 dispatch/round vs {sm['dispatches_loop']} "
                  f"in the per-run loop")
            ssm = rec.get("sharded_sweep_cost_model")
            if ssm:
                print(f"       sharded sweep R={ssm['r_runs']} × "
                      f"s={ssm['n_shards']}: "
                      f"{ssm['state_bytes_per_device'] / 1e6:.2f} MB/device, "
                      f"dense coll "
                      f"{ssm['dense_collective_bytes'] / 1e6:.2f} MB, halo "
                      f"{ssm['halo_collective_bytes'] / 1e6:.2f} MB "
                      f"({ssm['num_halo_rounds']} rounds)")
        if shape.kind == "train" and n_total:
            pm = rec["population_cost_model"]
            print(f"       population n_total={n_total} "
                  f"(cohort {cohort_size}, sampling={sampling}): host store "
                  f"{pm['host_store_bytes'] / 1e9:.2f} GB, "
                  f"h2d+d2h {pm['hostdev_bytes_round'] / 1e6:.2f} MB/round, "
                  f"peak device {pm['peak_device_bytes'] / 1e6:.2f} MB "
                  f"(n_total-free)")
        if shape.kind == "train" and mesh_agents \
                and "sharded" in rec.get("gossip_cost_model", {}):
            sh = rec["gossip_cost_model"]["sharded"]
            if "impls" in sh:
                coll = ", ".join(
                    f"{k} {v['collective_bytes'] / 1e6:.1f}MB"
                    for k, v in sh["impls"].items())
                print(f"       sharded over {mesh_agents}: cut edges "
                      f"{sh['num_cut_edges']}/{sh['num_directed_edges']}, "
                      f"{sh['num_halo_rounds']} halo rounds; "
                      f"collective/device: {coll}")
                comp = ", ".join(
                    f"{k} {v['collective_bytes'] / 1e6:.1f}MB"
                    f" ({v['payload_ratio_vs_f32']:.2f}x)"
                    for k, v in sh["compress"].items())
                print(f"       compressed halo/device: {comp}")
            m2d = rec["gossip_cost_model"].get("mesh2d")
            if m2d and "impls" in m2d:
                dense = m2d["impls"]["dense"]
                print(f"       2-D mesh A={m2d['n_agent_shards']} x "
                      f"M={m2d['n_model_shards']}: "
                      f"{dense['state_bytes_per_device'] / 1e6:.2f} MB/device "
                      f"(A·M-way scaling), agent-axis gossip "
                      f"{dense['gossip_collective_bytes'] / 1e6:.2f} MB, "
                      f"model-axis coll "
                      f"{dense['model_collective_bytes'] / 1e6:.2f} MB")
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec.update({"status": "fail", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()})
        print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=2, default=str)
    return rec


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="all", help="arch id or 'all'")
    p.add_argument("--shape", default="all",
                   choices=["all"] + list(SHAPES))
    p.add_argument("--mesh", default="single",
                   choices=["single", "multi", "both"])
    p.add_argument("--fused", type=int, default=0, metavar="H",
                   help="compile train steps as the fused H-step round "
                        "executor (0 = per-step; non-train shapes "
                        "unaffected)")
    p.add_argument("--state-layout", default="tree",
                   choices=["tree", "flat", "sharded"],
                   help="train-state engine: 'flat' compiles the single "
                        "(n_agents, D)-buffer hot loop and reports the "
                        "per-impl gossip cost model; 'sharded' compiles "
                        "the shard_map engine (agent dim block-sharded "
                        "over the mesh's data axes, repro.core.sharded — "
                        "sharded-layout archs only; non-train shapes "
                        "unaffected)")
    p.add_argument("--mesh-agents", type=int, default=None, metavar="N",
                   help="add the agent-sharded engine's cost model "
                        "(per-device + cut-edge collective bytes for the "
                        "flat buffer block-sharded over N devices; "
                        "repro.core.sharded) to train-shape records")
    p.add_argument("--mesh-model", type=int, default=None, metavar="M",
                   help="with --mesh-agents A, record the 2-D (A, M) mesh "
                        "byte model (analysis.mesh2d_cost_model): each "
                        "agent replica tensor-sharded over M model-axis "
                        "devices, gossip collectives on D/M-wide slices "
                        "over the agent axis only")
    p.add_argument("--fuse-update-mix", action="store_true",
                   help="compile train steps with Algorithm 1 lines 5-6 "
                        "fused into one tiled buffer pass "
                        "(kernels/update_mix.py; --state-layout flat); the "
                        "record gains analysis.roundfuse_cost_model either "
                        "way")
    p.add_argument("--gossip-compress", default="none", metavar="SPEC",
                   help="compile train steps with the compressed-gossip "
                        "subsystem (repro.core.compress: none | identity | "
                        "bf16 | int8 | topk:R) — the state gains the EF "
                        "residual buffer and the cost model records the "
                        "compressed payload bytes")
    p.add_argument("--sweep-runs", type=int, default=None, metavar="R",
                   help="compile train steps as the batched sweep engine "
                        "(repro.core.sweep): the carried state becomes the "
                        "(R, n_agents, D) lattice buffer and the record "
                        "gains the sweep memory/bytes prediction "
                        "(analysis.sweep_cost_model).  Needs --state-layout "
                        "flat (or sharded for the composed R×s lowering, "
                        "which with --mesh-agents N also records "
                        "analysis.sharded_sweep_cost_model) and --fused H")
    p.add_argument("--sweep-axis", default="seed",
                   choices=["seed", "h", "topology"],
                   help="lattice axis for --sweep-runs (see "
                        "launch.steps.sweep_lattice_configs)")
    p.add_argument("--n-total", type=int, default=None, metavar="N",
                   help="record the population-engine cost model "
                        "(repro.core.population: cohort-sampled FedDec with "
                        "host-resident (N, D) store and streamed cohorts — "
                        "analysis.population_cost_model) on train-shape "
                        "records")
    p.add_argument("--cohort-size", type=int, default=256, metavar="C",
                   help="active cohort size per round for --n-total")
    p.add_argument("--sampling", default="uniform",
                   choices=["uniform", "weighted", "stale"],
                   help="cohort sampler recorded alongside --n-total "
                        "(does not change the byte model)")
    p.add_argument("--staleness", type=float, default=0.0, metavar="BETA",
                   help="FedPAE staleness-tilt beta recorded alongside "
                        "--n-total (does not change the byte model)")
    p.add_argument("--out", default=RESULTS_DIR)
    args = p.parse_args()

    assert len(jax.devices()) == 512, "host-device override failed"
    archs = list(ARCH_NAMES) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                rec = run_one(arch, shape, multi, args.out,
                              fused_steps=args.fused or None,
                              state_layout=args.state_layout,
                              mesh_agents=args.mesh_agents,
                              mesh_model=args.mesh_model,
                              gossip_compress=args.gossip_compress,
                              sweep_runs=args.sweep_runs,
                              sweep_axis=args.sweep_axis,
                              n_total=args.n_total,
                              cohort_size=args.cohort_size,
                              sampling=args.sampling,
                              staleness=args.staleness,
                              fuse_update_mix=args.fuse_update_mix)
                if rec["status"] != "ok":
                    failures.append(rec)
    print(f"\n{len(failures)} failures / "
          f"{len(archs) * len(shapes) * len(meshes)} combos")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
