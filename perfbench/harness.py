"""One benchmark run of one cell: set-up, the timed window, the check.

Set-up builds what ``launch/train.py:train_loop`` builds on one chip, with
the same calls: the model, ``build_fed_setup``, the flat spec and state,
and the round executor ``core/flat.make_flat_feddec_round``.  The weights are
the benchmark's own (``weights.py``), the tokens a pool drawn on the device
(``traffic.py``).  Set-up then drives the executor through its first round
from the seed; that round compiles, and its losses and state are what the
reference checks.  The same executor and state go on into the window.

The window dispatches one round of H steps per call, as ``train_loop``
does, but pulls each round's losses to the host ``AHEAD`` rounds late, so
that the chip stays fed while the host stands still.  Once ``seconds``
have passed it sends nothing more, waits for every round it sent, and
reads the clock after that wait.  Garbage is collected and frozen before
the window, so that no collection of set-up's objects falls inside it.
With ``trace`` the window runs under the profiler, and the per-layer
metrics are read from the trace, the round's compiled text (its kernels,
and each instruction's phase, ``scopes.py``) and the reference's FLOP
count; every metric, end to end or per layer, is read by its own reader,
``metrics/<name>.py``.

After the window the peak memory is read, the program's state is freed,
and the reference runs the checked round.
"""

from __future__ import annotations

import contextlib
import gc
import math
import re
import shutil
import sys
import tempfile
import time
from functools import partial

import numpy as np

from perfbench import algorithm1, compare, counts, faults, scopes, traffic
from perfbench import trace as trace_lib
from perfbench.peaks import peaks_for
from perfbench.seeds import purpose_key
from perfbench.spec import HERE, Cell, arch_config, load_module
from perfbench.weights import make_weights

__all__ = ["run", "checked_round", "reference", "reference_model",
           "device_info", "COMPILE_EVENTS"]

SAME = faults.wrappers(None)

# rounds dispatched ahead of the one whose losses the window waits for
AHEAD = 2

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


@contextlib.contextmanager
def _compile_seconds():
    """Sum JAX's trace + lower + compile seconds while the block runs."""
    import jax

    out = {"s": 0.0, "events": 0}

    def listener(event, duration, **_):
        if event in COMPILE_EVENTS:
            out["s"] += duration
            out["events"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield out
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _ring_k(graph: str) -> int:
    if not graph.startswith("ring"):
        raise ValueError(f"the reference knows ring graphs only, got "
                         f"{graph!r}")
    return int(graph[4:] or 2)


def _build(cell: Cell, wrap=SAME) -> dict:
    """The program's executor, built as train_loop builds it; ``wrap``:
    (wrap_grad, wrap_lr, wrap_fed) of ``faults.wrappers``."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import FedConfig
    from repro.core import flat as flat_lib
    from repro.launch.steps import build_fed_setup
    from repro.models import build_model
    from repro.sharding import MeshAxes

    wrap_grad, wrap_lr, wrap_fed = wrap
    tr = cell.traffic
    if tr["optimizer"] != "sgd":
        raise ValueError("the reference implements the sgd optimizer only")
    cfg = arch_config(cell.config)
    model = build_model(cfg)
    fed = FedConfig(n_agents=tr["agents"], h=tr["h"], k=tr["k"],
                    graph=tr["graph"], gossip_impl=tr["gossip_impl"])
    axes = MeshAxes(("data",), "model", {"data": fed.n_agents, "model": 1})
    fcfg, n = build_fed_setup(cfg, axes, fed)
    fcfg = wrap_fed(fcfg)
    lr = tr["lr"]
    lr_fn = wrap_lr(lambda t: jnp.asarray(lr, jnp.float32))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    spec = flat_lib.make_flat_spec(shapes)
    round_fn = flat_lib.make_flat_feddec_round(
        fcfg, spec, wrap_grad(model.grad_fn()), lr_fn, optimizer=None,
        donate=True, delta_base=None,
        fuse_update_mix=tr["fuse_update_mix"])
    return dict(cfg=cfg, fcfg=fcfg, n=n, spec=spec, shapes=shapes,
                round_fn=round_fn)


def _start(cell: Cell, b: dict, seed: int) -> dict:
    """Weights, state, token pool and round key of one seed."""
    from repro.core import flat as flat_lib

    tr = cell.traffic
    make_params = partial(make_weights, purpose_key(seed, "weights"),
                          b["shapes"])
    state = flat_lib.init_flat_state(b["spec"], make_params(), b["n"])
    pool = traffic.token_pool(
        purpose_key(seed, "traffic"), vocab=b["cfg"].vocab_size,
        n_agents=b["n"], batch=tr["per_agent_batch"], seq_len=tr["seq_len"],
        h=tr["h"], rounds=tr["pool_rounds"], alpha=tr["alpha"])
    return dict(state=state, pool=pool, batches=traffic.round_batches(pool),
                make_params=make_params, key=purpose_key(seed, "rounds"))


def checked_round(b: dict, s: dict):
    """Drive the executor through its first round from the seed: returns
    the state after it, the round's losses, and each agent's change and
    deviation from the agents' mean, per segment.  The window goes on from
    the returned state."""
    state, metrics = b["round_fn"](s.pop("state"), s["batches"][0], s["key"])
    losses = np.asarray(metrics["loss"], np.float64)
    change, spread = compare.flat_norms(compare.segments(b["shapes"]),
                                        b["spec"], state.flat,
                                        s["make_params"]())
    return state, losses, change, spread


def _kernel_names(compiled_text: str) -> list[str]:
    """HLO names of the Mosaic kernels (``tpu_custom_call``) of a program."""
    return re.findall(r"%?([\w.\-]+) = [^\n]*custom_call_target="
                      r"\"tpu_custom_call\"", compiled_text)


def reference_model(cell: Cell):
    """The cell's plain model, ``references/<reference>.py``: its
    ``loss`` and ``flops_per_token``."""
    return load_module(HERE / "references"
                       / f"{cell.config['reference']}.py")


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, fault: str | None = None,
        keep_ctx: dict | None = None) -> dict:
    """Run the cell once; returns the result object (the last stdout
    line).  ``fault`` plants one of ``faults.FAULTS`` in the program;
    ``keep_ctx`` receives the readers' context, which under ``trace`` also
    holds the round's compiled ``text``, its ``scopes`` and the trace."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    tr, arch = cell.traffic, cell.config["arch"]
    h, batch, seq = tr["h"], tr["per_agent_batch"], tr["seq_len"]
    phases = {"start": time.time() - t_start}
    with _compile_seconds() as comp:
        b = _build(cell, faults.wrappers(fault))
        n, spec, round_fn = b["n"], b["spec"], b["round_fn"]
        s = _start(cell, b, seed)
        batches, key = s["batches"], s["key"]
        jax.block_until_ready(s["state"])
        phases["build_state_pool"] = time.time() - t_start - sum(phases.values())
        # the checked round: the window's own executor, call and feed
        state, warm_losses, change, spread = checked_round(b, s)
        phases["checked_round"] = time.time() - t_start - sum(
            phases.values())
        compile_s = comp["s"]
        if trace:
            text = round_fn.lower(state, batches[1 % len(batches)],
                                  key).compile().as_text()

        t_window = time.time()
        setup_s = t_window - t_start
        print("perfbench: set-up seconds " + " ".join(
            f"{k} {v:.3f}" for k, v in phases.items())
            + f" compile {compile_s:.3f}", file=sys.stderr)
        log_dir = tempfile.mkdtemp(prefix="perfbench-trace-") \
            if trace else None
        if trace:
            jax.profiler.start_trace(log_dir)
        events_before = comp["events"]
        rounds = failed = 0
        round_s, pending = [], []

        def pull():
            nonlocal failed
            with jax.profiler.TraceAnnotation("bench.loss_pull"):
                losses = np.asarray(pending.pop(0)["loss"])
            failed += int(not np.all(np.isfinite(losses)))
            round_s.append(time.perf_counter() - w0 - sum(round_s))

        gc.collect()
        gc.freeze()
        w0 = time.perf_counter()
        while time.perf_counter() - w0 < seconds or not rounds:
            with jax.profiler.TraceAnnotation("bench.pool_cycle"):
                feed = batches[(rounds + 1) % len(batches)]
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                state, metrics = round_fn(state, feed, key)
            pending.append(metrics)
            rounds += 1
            if len(pending) > AHEAD:
                pull()
        while pending:
            pull()
        window_s = time.perf_counter() - w0
        gc.unfreeze()
        print("perfbench: round seconds " + " ".join(
            f"{r:.4f}" for r in round_s), file=sys.stderr)
        if trace:
            jax.profiler.stop_trace()
        if comp["events"] > events_before:
            print(f"perfbench: {comp['events'] - events_before} compile "
                  f"events inside the window", file=sys.stderr)

    device = device_info(cell.chips)
    del state, metrics
    steps = rounds * h
    ctx = {"tokens": steps * n * batch * seq, "window_s": window_s,
           "memory_peak_bytes": device["memory_peak_bytes"],
           "setup_s": setup_s, "compile_s": compile_s, "steps": steps,
           "chips": cell.chips}
    breakdown = None
    if trace:
        try:
            tr_data = trace_lib.load_trace(log_dir)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        lo, hi = trace_lib.window_of(tr_data.spans)
        ids = sorted(tr_data.devices)[:cell.chips]
        busy = [trace_lib.busy_ns(tr_data.devices[i], lo, hi) for i in ids]
        device["busy_s"] = float(np.mean(busy)) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        ctx.update(
            trace=tr_data, lo=lo, hi=hi, device_ids=ids, text=text,
            scopes=scopes.hlo_scopes(text), kernels=_kernel_names(text),
            flops_per_step=reference_model(cell).flops_per_token(arch, seq)
            * n * batch * seq,
            peaks=peaks_for(device["kind"]),
            update_mix_bytes=counts.update_mix_bytes(n, spec.d)
            if tr["fuse_update_mix"] else None)
        d0 = tr_data.devices[ids[0]] if ids else []
        breakdown = {"device_ops": trace_lib.top_ops(d0, lo, hi),
                     "idle_gaps": trace_lib.idle_gaps(d0, tr_data.spans,
                                                      lo, hi)}
    result_metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            result_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if keep_ctx is not None:
        keep_ctx.update(ctx)

    ref = reference(cell, b, s)
    nums = compare.numbers(warm_losses, change, spread, ref)
    ok, checks = compare.judge(nums, cell.limits)
    result = {"correct": bool(ok and failed == 0
                              and np.all(np.isfinite(warm_losses))),
              "attempted": rounds, "failed": failed,
              "metrics": result_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # the compared numbers come last
    result["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def _finite(v: float):
    return v if math.isfinite(v) else None


def reference(cell: Cell, b: dict, s: dict, mm: str = "float32",
              fault: str | None = None):
    """The reference's readings of the checked round.  ``mm`` and ``fault``
    put a lower precision or a planted fault (``faults.py``) into the
    reference, where it stands in the program's place for the control."""
    import jax

    tr = cell.traffic
    n = b["n"]
    loss_fn = reference_model(cell).loss
    w = algorithm1.ring_metropolis(n, min(_ring_k(tr["graph"]),
                                          (n - 1) // 2 or 1))
    tokens, lr = s["pool"][0], tr["lr"]
    if fault == "frozen":
        lr = 0.0
    elif fault == "half_batch":
        tokens = tokens[..., :tokens.shape[-1] // 2]
    elif fault is not None and fault not in faults.FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    segs = compare.segments(b["shapes"])
    return algorithm1.reference_round(
        loss_fn, cell.config["arch"], s["make_params"](), tokens,
        w=faults.mixing_matrix(fault, w), h=tr["h"], k=b["fcfg"].k, lr=lr,
        key=s["key"], t0=1,
        segment_norms=partial(compare.tree_segment_norms, segs),
        mm=algorithm1.MATMULS[mm], devices=jax.devices()[:cell.chips])
