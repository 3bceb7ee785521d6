"""Serving driver: batched prefill + greedy decode with KV/state caches.

Builds the model, runs a full prefill to populate the caches (token-by-token
here — numerically the same cache state the chunked prefill would produce),
then decodes new tokens one step at a time.  Works for every assigned
architecture, including the sub-quadratic ones whose caches are O(1) in
sequence length.

Two entry points:

  * :func:`generate` — one shared parameter set for the whole batch (the
    classic serving path).
  * :func:`generate_personalized` — multi-tenant FedDec serving: request b
    serves *agent b*, whose weights are ``base + delta_b`` (the delta
    parameterization of repro.core.delta).  The deltas are applied with one
    vmapped unflatten and the whole batch runs through ONE vmapped decode
    step per token — B compiled dispatches per token (the naive per-agent
    loop) collapse to one.

The compiled decode step is cached per (model, long_variant) — repeated
``generate()`` calls with same-shaped requests reuse the compiled fn
instead of rebuilding ``jax.jit`` per call.
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import load_checkpoint
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.specs import concrete_batch
from repro.models import build_model

__all__ = ["generate", "generate_personalized"]


@functools.lru_cache(maxsize=32)
def _decode_step_fn(model, long_variant: bool):
    """Compiled shared-params decode step, cached across generate() calls.

    ``model`` is a frozen dataclass (hash = its ArchConfig), so the cache
    key is the architecture; jit itself re-specializes on shapes.  enc_out
    rides along as a traced argument (None for decoder-only archs).
    """
    def step(params, batch, caches, enc_out):
        return model.decode_step(params, batch, caches, enc_out=enc_out,
                                 long_variant=long_variant)
    return jax.jit(step)


@functools.lru_cache(maxsize=32)
def _personalized_step_fn(model, long_variant: bool):
    """Compiled per-request-params decode step: vmap over the batch axis.

    Every argument (params tree, batch dict, caches) carries a leading
    request axis; each vmap lane is a batch-1 decode with its own weights —
    one fused program instead of B sequential dispatches.
    """
    def step(params, batch, caches):
        return model.decode_step(params, batch, caches,
                                 long_variant=long_variant)
    return jax.jit(jax.vmap(step))


def _validate_prompt(prompt_tokens, max_new_tokens, temperature, cache_len):
    if prompt_tokens.ndim != 2:
        raise ValueError(
            f"prompt_tokens must be (B, S_prompt), got shape "
            f"{tuple(prompt_tokens.shape)}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    b, s_prompt = prompt_tokens.shape
    if s_prompt < 1:
        raise ValueError("prompt must contain at least one token")
    total = s_prompt + max_new_tokens
    if cache_len is None:
        cache_len = total
    elif cache_len < total:
        raise ValueError(
            f"cache_len={cache_len} cannot hold prompt ({s_prompt}) + "
            f"max_new_tokens ({max_new_tokens}) = {total} positions")
    return b, s_prompt, cache_len


def generate(model, params, prompt_tokens: jax.Array, *,
             max_new_tokens: int = 32, cache_len: int | None = None,
             enc_out: jax.Array | None = None,
             long_variant: bool = False,
             temperature: float = 0.0, key: jax.Array | None = None):
    """Greedy/temperature decode.  prompt_tokens: (B, S_prompt)."""
    b, s_prompt, cache_len = _validate_prompt(
        prompt_tokens, max_new_tokens, temperature, cache_len)
    caches = model.init_caches(b, cache_len, long_variant=long_variant,
                               dtype=jnp.float32)

    step = _decode_step_fn(model, long_variant)

    def one(tok, pos, caches):
        batch = {"tokens": tok,
                 "positions": jnp.full((b, 1), pos, jnp.int32)}
        if model.cfg.rope_kind == "mrope":
            batch["mrope_positions"] = jnp.full((3, b, 1), pos, jnp.int32)
        return step(params, batch, caches, enc_out)

    # prefill (token-by-token; produces the identical cache state)
    logits = None
    for t in range(s_prompt):
        logits, caches = one(prompt_tokens[:, t:t + 1], t, caches)

    out = [prompt_tokens]
    tok = None
    if key is None:
        key = jax.random.key(0)
    for i in range(max_new_tokens):
        if temperature > 0:
            key, k = jax.random.split(key)
            tok = jax.random.categorical(
                k, logits[:, -1] / temperature, axis=-1)[:, None]
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out.append(tok)
        logits, caches = one(tok, s_prompt + i, caches)
    return jnp.concatenate(out, axis=1)


def generate_personalized(model, flat_spec, base_row: jax.Array,
                          delta_rows: jax.Array | None,
                          prompt_tokens: jax.Array, *,
                          max_new_tokens: int = 32,
                          cache_len: int | None = None,
                          long_variant: bool = False,
                          temperature: float = 0.0,
                          key: jax.Array | None = None):
    """Multi-tenant decode: request b serves weights ``base + delta_b``.

    ``flat_spec`` is the model's FlatSpec (flat.make_flat_spec); ``base_row``
    is the shared (D,) base and ``delta_rows`` the (B, D) per-request dense
    deltas (decode a DeltaStore gather / delta-codec payload first;
    ``None`` serves the bare base to every request).  The per-request
    parameter trees are materialized with one whole-buffer add + unflatten,
    and each decoded token is ONE vmapped dispatch over the request axis,
    where the naive alternative makes B sequential ``generate`` calls with
    B full parameter sets.

    Decoder-only path (no enc_out): personalized serving targets the
    FedDec agent checkpoints, which are decoder-only throughout.
    """
    b, s_prompt, cache_len = _validate_prompt(
        prompt_tokens, max_new_tokens, temperature, cache_len)
    base_row = jnp.asarray(base_row).reshape(-1)
    if base_row.shape[0] != flat_spec.d:
        raise ValueError(f"base_row has D={base_row.shape[0]}, flat spec "
                         f"has D={flat_spec.d}")
    if delta_rows is None:
        rows = jnp.tile(base_row[None], (b, 1))
    else:
        delta_rows = jnp.asarray(delta_rows)
        if delta_rows.shape != (b, flat_spec.d):
            raise ValueError(
                f"delta_rows must be (B, D) = ({b}, {flat_spec.d}), got "
                f"{tuple(delta_rows.shape)}")
        rows = base_row[None] + delta_rows
    params = flat_spec.unflatten(rows)     # leaves carry a leading B axis

    caches1 = model.init_caches(1, cache_len, long_variant=long_variant,
                                dtype=jnp.float32)
    caches = jax.tree.map(
        lambda c: jnp.broadcast_to(c[None], (b,) + c.shape), caches1)

    step = _personalized_step_fn(model, long_variant)

    def one(tok, pos, caches):
        # every leaf gets a leading request axis; each lane is a batch-1
        # decode of its own agent
        batch = {"tokens": tok[:, None, :],
                 "positions": jnp.full((b, 1, 1), pos, jnp.int32)}
        if model.cfg.rope_kind == "mrope":
            batch["mrope_positions"] = jnp.full((b, 3, 1, 1), pos,
                                                jnp.int32)
        logits, caches = step(params, batch, caches)   # (B, 1, 1, V)
        return logits[:, 0], caches

    logits = None
    for t in range(s_prompt):
        logits, caches = one(prompt_tokens[:, t:t + 1], t, caches)

    out = [prompt_tokens]
    if key is None:
        key = jax.random.key(0)
    for i in range(max_new_tokens):
        if temperature > 0:
            key, k = jax.random.split(key)
            tok = jax.random.categorical(
                k, logits[:, -1] / temperature, axis=-1)[:, None]
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out.append(tok)
        logits, caches = one(tok, s_prompt + i, caches)
    return jnp.concatenate(out, axis=1)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="qwen1.5-4b")
    p.add_argument("--smoke", action="store_true", default=True)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--new-tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--ckpt", default=None,
                   help="checkpoint dir from launch/train.py")
    args = p.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    if args.ckpt:
        tree = load_checkpoint(args.ckpt)
        # serve the agent-0 slice of the federated stacked params
        params = jax.tree.map(lambda x: jnp.asarray(x)[0], tree["params"])

    enc_out = None
    if cfg.is_encoder_decoder:
        enc_batch = concrete_batch(cfg, None, args.batch, 8,
                                   jax.random.key(1), enc_len=8)
        enc_out = model.encode(params, enc_batch)

    prompt = jax.random.randint(jax.random.key(2),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    t0 = time.time()
    seqs = generate(model, params, prompt, max_new_tokens=args.new_tokens,
                    enc_out=enc_out, temperature=args.temperature)
    dt = time.time() - t0
    tput = args.batch * args.new_tokens / dt
    print(f"[serve] {cfg.name}: {args.batch}×{args.new_tokens} new tokens "
          f"in {dt:.1f}s ({tput:.1f} tok/s)")
    print("[serve] sample:", seqs[0, :24].tolist())


if __name__ == "__main__":
    main()
