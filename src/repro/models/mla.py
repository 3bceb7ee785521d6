"""Multi-head Latent Attention (DeepSeek-V2 §2.1, DeepSeek-V3 §2.1.1).

MLA compresses K/V into a low-rank latent c_kv (``kv_lora_rank`` wide) plus a
single shared RoPE key head; per-head keys/values are up-projections of the
latent.  The decode-time win: the cache stores only (latent, k_rope) —
~(512+64) floats/token for V3 instead of 2·128·128.

Prefill here expands K/V and reuses the chunked-attention machinery; decode
runs the **absorbed** form, attending entirely in latent space:

    score_t = q_nopeᵀ W_ukᵀ c_t + q_ropeᵀ k_rope_t
            = (W_uk q_nope)ᵀ c_t + …        (absorb W_uk into the query)
    out     = W_uv Σ_t p_t c_t              (absorb W_uv into the output)

which is how real serving engines run MLA and what the latent cache is for.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig
from repro.models import attention as attn
from repro.models import layers

__all__ = ["init_mla", "mla_attention", "init_mla_cache"]


def init_mla(key, d: int, num_heads: int, cfg: MLAConfig,
             dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 8)
    qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = layers.init_dense(ks[0], (d, cfg.q_lora_rank), dtype)
        p["q_norm"] = layers.init_rms_norm(cfg.q_lora_rank, dtype)
        p["wq_b"] = layers.init_dense(
            ks[1], (cfg.q_lora_rank, num_heads, qk_dim), dtype,
            fan_in=cfg.q_lora_rank)
    else:
        p["wq"] = layers.init_dense(ks[0], (d, num_heads, qk_dim), dtype,
                                    fan_in=d)
    p["wkv_a"] = layers.init_dense(
        ks[2], (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), dtype)
    p["kv_norm"] = layers.init_rms_norm(cfg.kv_lora_rank, dtype)
    p["wk_b"] = layers.init_dense(
        ks[3], (cfg.kv_lora_rank, num_heads, cfg.qk_nope_head_dim), dtype,
        fan_in=cfg.kv_lora_rank)
    p["wv_b"] = layers.init_dense(
        ks[4], (cfg.kv_lora_rank, num_heads, cfg.v_head_dim), dtype,
        fan_in=cfg.kv_lora_rank)
    p["wo"] = layers.init_dense(
        ks[5], (num_heads, cfg.v_head_dim, d), dtype,
        fan_in=num_heads * cfg.v_head_dim)
    return p


def init_mla_cache(batch: int, cache_len: int, cfg: MLAConfig,
                   dtype=jnp.bfloat16) -> dict:
    return {
        "latent": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cache_len, cfg.qk_rope_head_dim), dtype),
        "positions": jnp.full((cache_len,), -1, dtype=jnp.int32),
        "index": jnp.zeros((), dtype=jnp.int32),
    }


def _project_q(params, x, cfg: MLAConfig, num_heads, compute_dtype):
    if "wq_a" in params:
        ql = layers.dense(params["wq_a"], x, compute_dtype=compute_dtype)
        ql = layers.rms_norm(params["q_norm"], ql)
        q = layers.dense(params["wq_b"], ql, compute_dtype=compute_dtype)
    else:
        q = layers.dense(params["wq"], x, compute_dtype=compute_dtype)
    return jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)  # nope, rope


def _project_latent(params, x, cfg: MLAConfig, compute_dtype):
    kv = layers.dense(params["wkv_a"], x, compute_dtype=compute_dtype)
    latent, k_rope = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
    latent = layers.rms_norm(params["kv_norm"], latent)
    return latent, k_rope  # (B,S,rank), (B,S,rope_dim)


def mla_attention(params: dict, x: jax.Array, positions: jax.Array, *,
                  num_heads: int, cfg: MLAConfig,
                  rope_theta: float = 10_000.0,
                  window: int = 0,
                  cache: dict | None = None,
                  compute_dtype=jnp.bfloat16) -> tuple[jax.Array, dict | None]:
    """MLA forward.  Prefill when cache is None, absorbed decode otherwise."""
    q_nope, q_rope = _project_q(params, x, cfg, num_heads, compute_dtype)
    q_rope = layers.apply_rope(q_rope, positions, rope_theta)
    latent, k_rope = _project_latent(params, x, cfg, compute_dtype)
    # shared single-head rope key
    k_rope = layers.apply_rope(k_rope[..., None, :], positions,
                               rope_theta)[..., 0, :]
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5

    if cache is None:
        # ---- prefill: expand per-head K/V from the latent ------------------
        k_nope = layers.dense(params["wk_b"], latent,
                              compute_dtype=compute_dtype)   # (B,S,H,nope)
        v = layers.dense(params["wv_b"], latent,
                         compute_dtype=compute_dtype)        # (B,S,H,vdim)
        k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                    k_nope.shape[:3] + (cfg.qk_rope_head_dim,))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
        # pad V to the QK head dim so we can reuse the GQA chunked kernel,
        # then slice back (vdim ≤ qk_dim always holds for DeepSeek configs)
        qk_dim = q.shape[-1]
        v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_dim - v.shape[-1])))
        out = attn._chunked_prefill(q, k, v_pad, positions, positions,
                                    scale=scale, window=window, causal=True)
        out = out[..., :cfg.v_head_dim]
        new_cache = None
    else:
        # ---- absorbed decode: attend in latent space -----------------------
        s_cache = cache["latent"].shape[1]
        slot = cache["index"] % s_cache
        lc = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], latent.astype(cache["latent"].dtype), slot, axis=1)
        rc = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), slot, axis=1)
        pos_now = positions[0, -1]
        posc = jax.lax.dynamic_update_slice_in_dim(
            cache["positions"], pos_now[None].astype(jnp.int32), slot, axis=0)
        new_cache = {"latent": lc, "k_rope": rc, "positions": posc,
                     "index": cache["index"] + 1}
        # absorb W_uk into the query: (B,1,H,nope) @ (rank,H,nope) → latent dim
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope,
                           params["wk_b"]["w"].astype(compute_dtype))
        scores = jnp.einsum("bshr,btr->bhst", q_lat,
                            lc.astype(compute_dtype),
                            preferred_element_type=jnp.float32)
        scores += jnp.einsum("bshr,btr->bhst", q_rope,
                             rc.astype(compute_dtype),
                             preferred_element_type=jnp.float32)
        scores *= scale
        valid = (posc >= 0) & (posc <= pos_now)
        if window > 0:
            valid &= posc > pos_now - window
        scores = jnp.where(valid[None, None, None, :], scores, attn.NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhst,btr->bshr", probs, lc.astype(probs.dtype))
        # absorb W_uv into the output
        out = jnp.einsum("bshr,rhv->bshv", ctx.astype(compute_dtype),
                         params["wv_b"]["w"].astype(compute_dtype))

    y = jnp.einsum("bshv,hvo->bso", out.astype(compute_dtype),
                   params["wo"]["w"].astype(compute_dtype))
    return y, new_cache
