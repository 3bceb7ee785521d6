"""ShapeDtypeStruct stand-ins for every model input (no device allocation).

``batch_schema`` is the one source of truth for a batch's leaves and
shapes; ``train_batch_specs`` / ``decode_batch_specs`` return it as
ShapeDtypeStructs, and ``concrete_batch`` builds small real batches from
it for tests and examples.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeConfig

__all__ = ["train_batch_specs", "decode_batch_specs", "concrete_batch",
           "batch_schema"]


def batch_schema(cfg: ArchConfig, n_agents: int | None, batch: int,
                 seq: int, *, decode: bool = False,
                 enc_len: int | None = None) -> dict[str, tuple]:
    """(shape, dtype) schema for one batch; agent dim prepended if given."""
    lead = (n_agents,) if n_agents is not None else ()

    def tok(shape):
        return (lead + shape, jnp.int32)

    def emb(shape):
        return (lead + shape, cfg.compute_dtype)

    schema: dict[str, tuple] = {
        "tokens": tok((batch, seq)),
        "positions": tok((batch, seq)),
    }
    if cfg.rope_kind == "mrope":
        # agent dim leads (vmap slices dim 0); per-agent layout is (3, B, S)
        schema["mrope_positions"] = (lead + (3, batch, seq), jnp.int32)
    if cfg.frontend == "vision" and not decode:
        schema["frontend_embeds"] = emb(
            (batch, cfg.frontend_positions, cfg.d_model))
    if cfg.is_encoder_decoder:
        el = enc_len if enc_len is not None else (4096 if decode else seq)
        if decode:
            # decode consumes the precomputed encoder memory, not raw frames
            schema["enc_out"] = emb((batch, el, cfg.d_model))
        else:
            schema["enc_embeds"] = emb((batch, el, cfg.d_model))
    return schema


def _structs(schema: dict[str, tuple]) -> dict[str, jax.ShapeDtypeStruct]:
    return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in schema.items()}


def train_batch_specs(cfg: ArchConfig, shape: ShapeConfig,
                      n_agents: int) -> dict:
    assert shape.kind in ("train", "prefill")
    per_agent = shape.global_batch // n_agents
    assert per_agent * n_agents == shape.global_batch, \
        (shape.global_batch, n_agents)
    return _structs(batch_schema(cfg, n_agents, per_agent, shape.seq_len))


def decode_batch_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    assert shape.is_decode
    return _structs(batch_schema(cfg, None, shape.global_batch, 1,
                                 decode=True))


def concrete_batch(cfg: ArchConfig, n_agents: int | None, batch: int,
                   seq: int, key: jax.Array, *, decode: bool = False,
                   enc_len: int | None = None) -> dict:
    """Small real batch following the same schema (tests/examples)."""
    schema = batch_schema(cfg, n_agents, batch, seq, decode=decode,
                          enc_len=enc_len)
    out = {}
    for name, (shape, dtype) in schema.items():
        key, k = jax.random.split(key)
        if name == "tokens":
            out[name] = jax.random.randint(k, shape, 0, cfg.vocab_size)
        elif name == "positions":
            out[name] = jnp.broadcast_to(
                jnp.arange(shape[-1], dtype=jnp.int32), shape)
        elif name == "mrope_positions":
            out[name] = jnp.broadcast_to(
                jnp.arange(shape[-1], dtype=jnp.int32), shape)
        else:
            out[name] = (jax.random.normal(k, shape) * 0.02).astype(dtype)
    return out
