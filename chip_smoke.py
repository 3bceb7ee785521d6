#!/usr/bin/env python3
"""Drive FedDec's training and serving path once on a TPU, at full width.

    python3 chip_smoke.py              # one chip: phases a-d
    python3 chip_smoke.py --chips 4    # four chips: the sharded engine only

The model is the trainer's default LM, ``launch.train.tiny_lm_config()``:
d_model 768, 12 layers, vocab 32768, SwiGLU, f32, 156.5M parameters, random
weights from seed 0.  Four FedDec agents train on graph ring1 with H=5,
K=2, per-agent batch 2, sequence 512, for 10 steps (two server rounds),
through ``launch.train.train_loop`` — what

    python -m repro.launch.train --agents 4 --graph ring1 --h 5 --k 2 \\
        --batch 2 --seq 512 --steps 10

runs.  Phases on one chip:

  a  the default path: fused executor, flat layout, dense gossip;
  b  gossip_impl=pallas with fuse_update_mix (the fused update+mix kernel);
  c  gossip_impl=sparse (the ELL gossip kernel);
  d  serving: ``launch.serve.generate_personalized`` serves each of the 4
     agents phase c trained (base = the agents' mean, delta = row - base),
     8 prompt tokens and 8 new tokens, against one ``generate`` per agent.

With ``--chips 4`` it runs phase a on one device as the reference and the
sharded engine three ways: ``mesh_agents=4`` with dense (psum_scatter)
gossip, ``mesh_agents=4`` with sparse (ppermute halo) gossip, and the 2-D
engine ``mesh_agents=2, mesh_model=2``.

Checks: every loss is finite; phases b and c (and each sharded run) match
the reference's per-step losses to 1e-3 relative; the programs of b and c
hold ``tpu_custom_call`` (a Mosaic kernel, not an interpret-mode
expansion); personalized decode gives each agent the tokens its own
weights give; a sharded run holds its state on every device.  Each phase
prints its compile seconds, ``peak_bytes_in_use`` and losses; any failure
exits non-zero.  The last line of standard output is the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script refuses to run unless JAX's backend is a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import FedConfig  # noqa: E402
from repro.core import flat as flat_lib  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import generate, generate_personalized  # noqa: E402
from repro.launch.train import tiny_lm_config, train_loop  # noqa: E402
from repro.models import build_model  # noqa: E402

SEED = 0
N_AGENTS, GRAPH, H, K = 4, "ring1", 5, 2
BATCH, SEQ, STEPS = 2, 512, 10
PROMPT, NEW_TOKENS = 8, 8
RTOL = 1e-3

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def watch():
    """Sum JAX's trace + lower + compile seconds over the block, and find
    which programs lowered in it hold a Mosaic kernel (``tpu_custom_call``
    in the StableHLO JAX hands the compiler)."""
    out = {"compile_s": 0.0, "kernel_programs": []}

    def listener(event, duration, **_):
        if event in _COMPILE_EVENTS:
            out["compile_s"] += duration

    prev_dump = jax.config.read("jax_dump_ir_to")
    with tempfile.TemporaryDirectory() as dump:
        jax.config.update("jax_dump_ir_to", dump)
        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            yield out
        finally:
            jax.monitoring.unregister_event_duration_listener(listener)
            jax.config.update("jax_dump_ir_to", prev_dump)
        for name in sorted(os.listdir(dump)):
            with open(os.path.join(dump, name), errors="replace") as f:
                if "tpu_custom_call" in f.read():
                    out["kernel_programs"].append(name)


def device_bytes(key: str) -> list[int]:
    """``memory_stats()[key]`` per device (0 where the backend has none)."""
    return [int((d.memory_stats() or {}).get(key, 0)) for d in jax.devices()]


def shard_bytes(tree) -> list[int]:
    """Bytes of ``tree``'s arrays resident on each device."""
    per = dict.fromkeys(jax.devices(), 0)
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            per[shard.device] += shard.data.nbytes
    return list(per.values())


def emit(rec: dict) -> None:
    print(f"[chip_smoke] {json.dumps(rec)}", flush=True)


def train_phase(name: str, cfg, *, gossip_impl: str = "dense",
                fuse_update_mix: bool = False, mesh_agents: int | None = None,
                mesh_model: int | None = None, steps: int = STEPS,
                batch: int = BATCH, seq: int = SEQ):
    """One ``train_loop`` run: prints its record, checks its losses, and
    returns (record, final FedState)."""
    fed = FedConfig(n_agents=N_AGENTS, h=H, k=K, graph=GRAPH,
                    gossip_impl=gossip_impl)
    t0 = time.perf_counter()
    with watch() as w:
        state, losses = train_loop(
            cfg, fed, steps=steps, per_agent_batch=batch, seq_len=seq,
            fused=True, fuse_update_mix=fuse_update_mix,
            mesh_agents=mesh_agents, mesh_model=mesh_model, log_every=0,
            seed=SEED)
    rec = {"phase": name, "gossip_impl": gossip_impl,
           "fuse_update_mix": fuse_update_mix, "mesh_agents": mesh_agents,
           "mesh_model": mesh_model, "wall_s": time.perf_counter() - t0,
           "compile_s": w["compile_s"],
           "kernel_programs": len(w["kernel_programs"]),
           "state_bytes": sum(l.nbytes
                              for l in jax.tree.leaves(state.params)),
           "state_bytes_per_device": shard_bytes(state.params),
           "peak_bytes_in_use": device_bytes("peak_bytes_in_use"),
           "losses": [float(v) for v in losses]}
    emit(rec)
    check(len(rec["losses"]) == steps, f"{name}: {len(losses)} losses")
    check(all(math.isfinite(v) for v in rec["losses"]),
          f"{name}: non-finite loss {rec['losses']}")
    return rec, state


def agree(rec: dict, ref: dict) -> float:
    """Largest per-step relative loss difference against the reference;
    fails the phase beyond RTOL."""
    err = max(abs(a - b) / abs(b) for a, b in zip(rec["losses"],
                                                  ref["losses"]))
    print(f"[chip_smoke] {rec['phase']} vs {ref['phase']}: max relative "
          f"loss difference {err!r}", flush=True)
    check(err <= RTOL, f"{rec['phase']}: losses differ from phase "
          f"{ref['phase']} by {err:.3g} relative (> {RTOL})")
    return err


def serve_phase(cfg, state, *, prompt_len: int = PROMPT,
                new_tokens: int = NEW_TOKENS) -> dict:
    """Phase d: personalized decode of every trained agent, checked
    against one plain ``generate`` per agent with that agent's weights."""
    model = build_model(cfg)
    spec = flat_lib.make_flat_spec_from_stacked(state.params)
    rows = spec.flatten(state.params)                      # (n_agents, D)
    base = rows.mean(axis=0)
    deltas = rows - base[None]
    del rows
    n = deltas.shape[0]
    prompt = jax.random.randint(jax.random.key(SEED + 3), (n, prompt_len),
                                0, cfg.vocab_size)
    t0 = time.perf_counter()
    # full f32 matmuls: the batched and per-agent decodes then differ only
    # by rounding, far below any argmax gap
    with watch() as w, jax.default_matmul_precision("highest"):
        tokens = generate_personalized(model, spec, base, deltas, prompt,
                                       max_new_tokens=new_tokens)
        tokens.block_until_ready()
        t_batched = time.perf_counter() - t0
        naive = jnp.concatenate([
            generate(model, spec.unravel(base + deltas[i]),
                     prompt[i:i + 1], max_new_tokens=new_tokens)
            for i in range(n)])
    rec = {"phase": "d", "wall_s": time.perf_counter() - t0,
           "batched_decode_s": t_batched, "compile_s": w["compile_s"],
           "peak_bytes_in_use": device_bytes("peak_bytes_in_use"),
           "tokens": tokens.tolist(), "per_agent_tokens": naive.tolist()}
    emit(rec)
    check(tokens.shape == (n, prompt_len + new_tokens),
          f"d: tokens shape {tokens.shape}")
    check(bool(((tokens >= 0) & (tokens < cfg.vocab_size)).all()),
          "d: token id out of range")
    check(bool((tokens == naive).all()),
          "d: personalized tokens differ from per-agent generate")
    return rec


def run_one_chip(cfg, **shape) -> list[dict]:
    """Phases a, b, c, then d serving c's agents; ``shape`` overrides
    steps / batch / seq.  The training phases run first so that each one's
    ``peak_bytes_in_use`` (a process high-water mark) is not serving's."""
    recs, state = [], None
    for name, impl, fuse in (("a", "dense", False), ("b", "pallas", True),
                             ("c", "sparse", False)):
        state = None                        # free the previous phase's agents
        rec, state = train_phase(name, cfg, gossip_impl=impl,
                                 fuse_update_mix=fuse, **shape)
        recs.append(rec)
        if name == "a":
            continue
        agree(rec, recs[0])
        if jax.default_backend() == "tpu":
            check(rec["kernel_programs"] > 0,
                  f"{name}: no program holds tpu_custom_call — the Pallas "
                  f"kernels did not compile for the chip")
    recs.append(serve_phase(cfg, state))
    return recs


def run_four_chips(cfg, **shape) -> list[dict]:
    """Phase a on one device, then the sharded engine on four."""
    ref, state = train_phase("ref", cfg, gossip_impl="dense", **shape)
    del state
    recs = [ref]
    for name, impl, agents, model in (("s1d", "dense", 4, None),
                                      ("s1s", "sparse", 4, None),
                                      ("s2d", "dense", 2, 2)):
        rec, state = train_phase(name, cfg, gossip_impl=impl,
                                 mesh_agents=agents, mesh_model=model,
                                 **shape)
        del state
        recs.append(rec)
        agree(rec, ref)
        check_spread(rec)
    return recs


def check_spread(rec: dict, n_dev: int = 4) -> None:
    """A sharded run holds its agents' state on every device — at least
    half of an even share each, in the returned state and in the
    ``peak_bytes_in_use`` high-water mark (which a backend without memory
    stats reports as 0, and so skips)."""
    floor = rec["state_bytes"] // n_dev // 2
    held = rec["state_bytes_per_device"][:n_dev]
    check(len(held) == n_dev and min(held) >= floor,
          f"{rec['phase']}: per-device state bytes {held} (want >= "
          f"{floor} on each of {n_dev} devices)")
    peak = rec["peak_bytes_in_use"][:n_dev]
    check(not any(peak) or min(peak) >= floor,
          f"{rec['phase']}: per-device peak bytes {peak} (want >= {floor} "
          f"on each of {n_dev} devices)")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the sharded engine and its "
                        "one-device reference, on four chips")
    args = p.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not 'tpu'; this "
              f"script runs only on the chip", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2
    print(f"[chip_smoke] compile cache: {enable_compile_cache()}",
          flush=True)
    cfg = tiny_lm_config()
    if args.chips == 4:
        run_four_chips(cfg)
    else:
        run_one_chip(cfg)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
