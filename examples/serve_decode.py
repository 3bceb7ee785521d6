"""Serve a small model with batched requests (decode path demo).

Instantiates the reduced variant of any assigned architecture, prefills a
batch of prompts, and greedily decodes continuations using the same
KV/state-cache machinery the full-size configs use — including the
O(1)-state sub-quadratic paths (mamba2, recurrentgemma) and MLA's
compressed latent cache (deepseek).

Run:  PYTHONPATH=src python examples/serve_decode.py --arch mamba2-2.7b
"""

import argparse
import time

import jax

from repro.configs import ARCH_NAMES, get_config
from repro.launch.serve import generate
from repro.launch.specs import concrete_batch
from repro.models import build_model


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="recurrentgemma-9b",
                   choices=list(ARCH_NAMES))
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=12)
    p.add_argument("--new-tokens", type=int, default=20)
    p.add_argument("--temperature", type=float, default=0.8)
    args = p.parse_args()

    cfg = get_config(args.arch).smoke()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    print(f"[serve] {cfg.name}: {model.param_count(params):,} params, "
          f"cache kind: "
          f"{'O(1) state' if cfg.arch_type in ('ssm', 'hybrid') else 'KV'}")

    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = model.encode(
            params, concrete_batch(cfg, None, args.batch, 8,
                                   jax.random.key(1), enc_len=8))

    prompts = jax.random.randint(
        jax.random.key(2), (args.batch, args.prompt_len), 0, cfg.vocab_size)
    t0 = time.time()
    seqs = generate(model, params, prompts,
                    max_new_tokens=args.new_tokens, enc_out=enc_out,
                    temperature=args.temperature, key=jax.random.key(3))
    dt = time.time() - t0
    print(f"[serve] {args.batch} requests × {args.new_tokens} tokens in "
          f"{dt:.1f}s ({args.batch * args.new_tokens / dt:.1f} tok/s)")
    for i in range(min(2, args.batch)):
        prompt = seqs[i, :args.prompt_len].tolist()
        cont = seqs[i, args.prompt_len:].tolist()
        print(f"[req {i}] prompt={prompt} → continuation={cont}")


if __name__ == "__main__":
    main()
