"""Public model facade: build any ArchConfig into init / loss / decode fns.

This is the surface the training loop, the FedDec step and the serving path
all consume — they never touch layer internals.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer

__all__ = ["Model", "build_model"]


@dataclasses.dataclass(frozen=True)
class Model:
    """Bound (config, functions) bundle for one architecture."""

    cfg: ArchConfig

    # ---- parameters --------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        return transformer.init_model(key, self.cfg)

    def param_count(self, params: Any | None = None) -> int:
        if params is None:
            params = jax.eval_shape(self.init, jax.random.key(0))
        return sum(int(jnp.prod(jnp.asarray(l.shape)))
                   for l in jax.tree.leaves(params))

    # ---- training ----------------------------------------------------------
    def logits(self, params: dict, batch: dict, *, impl: str = "xla",
               remat: bool = True):
        logits, aux, _, _ = transformer.forward(
            params, batch, self.cfg, impl=impl, remat=remat)
        return logits, aux

    def loss(self, params: dict, batch: dict, key: jax.Array | None = None,
             *, impl: str = "xla", remat: bool = True) -> jax.Array:
        """Next-token cross entropy (+ MoE aux), masked to text targets.

        CE is computed as lse(logits) − logits[target] with f32 *reductions*
        only — the (B, S, V) logits are never upcast/copied to f32, which at
        a 262k vocab is the difference between ~0.6 GB and ~10 GB of live
        activations per microbatch.
        """
        del key
        logits, aux = self.logits(params, batch, impl=impl, remat=remat)
        tokens = batch["tokens"]
        targets = tokens[:, 1:]
        lg = logits[:, :-1]
        m = jax.lax.stop_gradient(lg.max(axis=-1, keepdims=True))
        shifted = lg - m
        sumexp = jnp.sum(jnp.exp(shifted.astype(jnp.float32)), axis=-1)
        lse = jnp.log(sumexp) + m[..., 0].astype(jnp.float32)
        gold = jnp.take_along_axis(lg, targets[..., None],
                                   axis=-1)[..., 0].astype(jnp.float32)
        nll = lse - gold  # (B, S-1)
        mask = jnp.ones_like(targets, dtype=jnp.float32)
        if self.cfg.frontend == "vision" and self.cfg.frontend_positions:
            # no next-token loss on image-patch positions
            pos = jnp.arange(targets.shape[1])[None]
            mask = (pos >= self.cfg.frontend_positions).astype(jnp.float32)
        loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        if self.cfg.moe is not None:
            loss = loss + self.cfg.moe.router_aux_weight * aux
        return loss

    def grad_fn(self, *, impl: str = "xla", remat: bool = True):
        """Single-agent (params, batch, key) → (loss, grads) for FedDec,
        under the ``feddec.grad`` scope (the per-agent forward/backward)."""
        def fn(params, batch, key):
            with jax.named_scope("feddec.grad"):
                return jax.value_and_grad(
                    lambda p: self.loss(p, batch, key, impl=impl,
                                        remat=remat))(params)
        return fn

    # ---- serving -----------------------------------------------------------
    def init_caches(self, batch: int, cache_len: int, *,
                    long_variant: bool = False, dtype=jnp.bfloat16) -> dict:
        return transformer.init_decode_caches(
            self.cfg, batch, cache_len, long_variant=long_variant,
            dtype=dtype)

    def encode(self, params: dict, batch: dict) -> jax.Array | None:
        """Precompute encoder memory (enc-dec archs) for the decode loop."""
        if not self.cfg.is_encoder_decoder:
            return None
        return transformer._encode(params, self.cfg, batch, "xla")

    def decode_step(self, params: dict, batch: dict, caches: dict, *,
                    enc_out: jax.Array | None = None,
                    long_variant: bool = False):
        """One-token decode.  batch['tokens'] is (B, 1).

        Returns (logits (B, 1, V), new_caches).
        """
        logits, _, new_caches, _ = transformer.forward(
            params, batch, self.cfg, caches=caches, enc_out=enc_out,
            long_variant=long_variant, remat=False)
        return logits, new_caches


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg=cfg)
