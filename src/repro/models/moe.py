"""Mixture-of-Experts layer (DeepSeekMoE-style: shared + routed experts).

Token-choice top-k routing with an expert-capacity buffer, implemented the
TPU-native way:

  * router top-k over E experts (softmax probs, renormalised top-k weights);
  * position-in-expert computed with a **sort-based rank** (no (N, E, C)
    one-hot dispatch tensor — at DeepSeek-V3 scale, 32k tokens × 256 experts
    × 1.3k capacity would be ~10¹⁰ elements);
  * tokens scattered into an (E, C, d) buffer, experts run as one batched
    matmul (E sharded over the `model`/expert-parallel axis — XLA turns the
    scatter/gather across the sharded E dim into the all-to-all of classic
    expert parallelism);
  * gather + weighted combine; overflowing tokens (rank ≥ C) are dropped —
    their residual path carries them (standard capacity-factor semantics).

Shared experts are algebraically merged into one wider always-on MLP
(S experts of width f ≡ one expert of width S·f).

The auxiliary load-balance loss is the switch-style E·Σ f_e·p̄_e.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models import layers

__all__ = ["init_moe", "moe_layer", "expert_capacity"]


def expert_capacity(num_tokens: int, cfg: MoEConfig) -> int:
    c = math.ceil(num_tokens * cfg.top_k / cfg.num_experts
                  * cfg.capacity_factor)
    return max(1, min(num_tokens, c))


def init_moe(key, d: int, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    kr, ki, kg, ko, ks = jax.random.split(key, 5)
    e, f = cfg.num_experts, cfg.d_ff_expert
    p = {
        "router": layers.init_dense(kr, (d, e), jnp.float32),  # router in f32
        "wi": layers.init_dense(ki, (e, d, f), dtype, fan_in=d),
        "wg": layers.init_dense(kg, (e, d, f), dtype, fan_in=d),
        "wo": layers.init_dense(ko, (e, f, d), dtype, fan_in=f),
    }
    if cfg.num_shared:
        p["shared"] = layers.init_mlp(ks, d, cfg.num_shared * f, "swiglu",
                                      dtype)
    return p


def _rank_within_expert(flat_expert: jax.Array, num_experts: int):
    """rank[i] = #{j : expert[j] == expert[i], order[j] < order[i]}.

    Stable-sort based: sort by expert id, subtract each expert segment's
    start offset, scatter ranks back to the original order.
    """
    nk = flat_expert.shape[0]
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    counts = jnp.bincount(flat_expert, length=num_experts)
    seg_start = jnp.cumsum(counts) - counts                  # (E,)
    rank_sorted = jnp.arange(nk) - seg_start[sorted_expert]
    return jnp.zeros((nk,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))


def moe_layer(params: dict, x: jax.Array, cfg: MoEConfig, *,
              compute_dtype=jnp.bfloat16,
              capacity: int | None = None) -> tuple[jax.Array, jax.Array]:
    """Apply the MoE block.

    Args:
      x: (B, S, d) activations.
      capacity: expert capacity override (None ⇒ from capacity_factor).

    Returns:
      (out (B, S, d), aux_load_balance_loss scalar f32)
    """
    b, s, d = x.shape
    n = b * s
    e, k = cfg.num_experts, cfg.top_k
    c = capacity if capacity is not None else expert_capacity(n, cfg)
    tokens = x.reshape(n, d)

    # ---- routing (f32 for stability) --------------------------------------
    logits = layers.dense(params["router"], tokens.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # (N, E)
    top_p, top_e = jax.lax.top_k(probs, k)                   # (N, k)
    weights = top_p / (top_p.sum(-1, keepdims=True) + 1e-9)  # renormalise

    # ---- dispatch ----------------------------------------------------------
    flat_e = top_e.reshape(n * k)
    rank = _rank_within_expert(flat_e, e)                    # (N·k,)
    keep = rank < c
    buf = jnp.zeros((e, c, d), dtype=compute_dtype)
    tok_rep = jnp.repeat(tokens.astype(compute_dtype), k, axis=0)
    # dropped tokens are routed to a clipped slot then masked to zero
    safe_rank = jnp.where(keep, rank, 0)
    contrib = jnp.where(keep[:, None], tok_rep, 0.0)
    buf = buf.at[flat_e, safe_rank].add(contrib, mode="drop")

    # ---- expert FFN (batched over E; swiglu) -------------------------------
    wi = params["wi"]["w"].astype(compute_dtype)
    wg = params["wg"]["w"].astype(compute_dtype)
    wo = params["wo"]["w"].astype(compute_dtype)
    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    h = jax.nn.silu(g) * h
    expert_out = jnp.einsum("ecf,efd->ecd", h, wo)           # (E, C, d)

    # ---- combine ------------------------------------------------------------
    gathered = expert_out[flat_e, safe_rank]                 # (N·k, d)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    wflat = weights.reshape(n * k, 1).astype(compute_dtype)
    combined = (gathered * wflat).reshape(n, k, d).sum(axis=1)
    out = combined.reshape(b, s, d)

    # ---- shared experts -----------------------------------------------------
    if "shared" in params:
        out = out + layers.mlp(params["shared"], x, "swiglu",
                               compute_dtype=compute_dtype)

    # ---- load-balance aux loss (switch-style) -------------------------------
    frac = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / (n * k)
    mean_p = probs.mean(axis=0)
    aux = e * jnp.sum(frac * mean_p)

    return out.astype(x.dtype), aux
