"""Grouped-query attention with causal / sliding-window masking and KV cache.

One implementation serves all the GQA-family architectures (qwen, gemma3,
mistral, nemotron, recurrentgemma's local-attn blocks, seamless, qwen2-vl):

  * prefill (``cache=None``): full-sequence causal attention, optionally
    windowed; the compute can route through the Pallas flash kernel
    (``impl='pallas'``) or the XLA einsum path (``impl='xla'``, numerically
    identical, used on CPU).
  * decode (``cache`` given): one query token against a (possibly rolling)
    cache.  The cache stores per-slot absolute positions so the same masking
    logic covers full caches, sliding windows and the ring buffer used by the
    ``long_500k`` windowed variant.

Cross-attention (seamless decoder) reuses the same params/apply with
``kv_override``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers

__all__ = [
    "init_attention", "attention", "init_cache", "NEG_INF",
]

NEG_INF = -1e30


def init_attention(key, d: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, bias: bool = False,
                   dtype=jnp.float32) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": layers.init_dense(kq, (d, num_heads, head_dim), dtype,
                                bias=bias, fan_in=d),
        "wk": layers.init_dense(kk, (d, num_kv_heads, head_dim), dtype,
                                bias=bias, fan_in=d),
        "wv": layers.init_dense(kv, (d, num_kv_heads, head_dim), dtype,
                                bias=bias, fan_in=d),
        "wo": layers.init_dense(ko, (num_heads, head_dim, d), dtype,
                                fan_in=num_heads * head_dim),
    }


def init_cache(batch: int, cache_len: int, num_kv_heads: int, head_dim: int,
               dtype=jnp.bfloat16) -> dict:
    """Empty KV cache.  ``positions`` = -1 marks unfilled slots."""
    return {
        "k": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype),
        "positions": jnp.full((cache_len,), -1, dtype=jnp.int32),
        "index": jnp.zeros((), dtype=jnp.int32),
    }


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,S,H,hd), k: (B,T,Kv,hd) → scores (B,Kv,G,S,T)."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, s, kv, h // kv, hd)
    return jnp.einsum("bskgh,btkh->bkgst", qg, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: (B,Kv,G,S,T), v: (B,T,Kv,hd) → (B,S,H,hd).

    The PV contraction runs in v's dtype (bf16 on TPU) — probs are cast
    down after the f32 softmax, exactly like the flash kernel; keeping them
    f32 here doubled the dominant prefill traffic/collective terms
    (§Perf iteration B2).
    """
    b, kv, g, s, _ = probs.shape
    out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v)
    return out.reshape(b, s, kv * g, v.shape[-1])


def _mask_from_positions(qpos: jax.Array, kpos: jax.Array,
                         window: int) -> jax.Array:
    """(..., S, T) bool mask from absolute positions; window<=0 ⇒ causal."""
    mask = kpos[..., None, :] <= qpos[..., :, None]
    if window > 0:
        mask &= kpos[..., None, :] > qpos[..., :, None] - window
    return mask


def _attend_block(q, k, v, qpos, kpos, *, scale, window, causal):
    """Dense attention on one query block.  Shapes: q (B,C,H,hd), k/v (B,T,Kv,hd)."""
    scores = _gqa_scores(q, k) * scale  # (B,Kv,G,C,T) f32
    if causal:
        mask = _mask_from_positions(qpos, kpos, window)  # (B,C,T)
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, v)


def _chunked_prefill(q, k, v, qpos, kpos, *, scale, window, causal,
                     chunk: int = 512) -> jax.Array:
    """Memory-efficient prefill: scan over query chunks (O(C·T) live scores).

    The XLA analogue of the Pallas flash kernel's outer loop — keeps the
    (S, T) score matrix from ever materialising (at 32k² that would be
    ~4 GB/head in f32).  Numerics identical to the dense path.

    Two deliberate memory moves:
      * masks are rebuilt per chunk from ``iota`` + the chunk index, never
        passed through the scan — otherwise XLA stacks an (NC, C, T) pred
        tensor into the loop carry (~340 MB/layer at 32k);
      * the chunk body is ``jax.checkpoint``-ed so the layer's backward
        recomputes per-chunk probs instead of stashing (NC, H, C, T) f32
        residuals — the flash-backward trade.

    Masking assumes queries are in sequence order (true for every assigned
    arch; ``qpos``/``kpos`` remain the source of truth for RoPE, which is
    applied before chunking).
    """
    b, s, h, hd = q.shape
    if s % chunk:
        # fall back to one dense block for ragged/short sequences
        return _attend_block(q, k, v, qpos, kpos, scale=scale, window=window,
                             causal=causal)
    nc = s // chunk
    qc = q.reshape(b, nc, chunk, h, hd).swapaxes(0, 1)      # (NC,B,C,H,hd)
    t = k.shape[1]

    @jax.checkpoint
    def attend_chunk(qi, i):
        q0 = i * chunk
        qpos_i = (q0 + jnp.arange(chunk))[None]             # (1, C)
        kpos_i = jnp.arange(t)[None]                        # (1, T)
        return _attend_block(qi, k, v, qpos_i, kpos_i, scale=scale,
                             window=window, causal=causal)

    def body(_, inp):
        qi, i = inp
        return None, attend_chunk(qi, i)

    _, outs = jax.lax.scan(body, None, (qc, jnp.arange(nc)))
    return outs.swapaxes(0, 1).reshape(b, s, h, hd)


def attention(params: dict, x: jax.Array, positions: jax.Array, *,
              num_kv_heads: int, head_dim: int,
              window: int = 0,
              rope_kind: str = "rope", rope_theta: float = 10_000.0,
              mrope_positions: jax.Array | None = None,
              cache: dict | None = None,
              kv_override: jax.Array | None = None,
              causal: bool = True,
              compute_dtype=jnp.bfloat16,
              chunked_prefill: bool = True,
              impl: str = "xla") -> tuple[jax.Array, dict | None]:
    """Apply GQA attention.

    Args:
      x: (B, S, d) input activations.
      positions: (B, S) absolute token positions (for RoPE + cache masking).
      window: sliding-window size (0 ⇒ full causal).
      cache: KV cache dict (decode mode) or None (prefill).
      kv_override: (B, T, d) encoder memory for cross-attention (no cache,
        no causal mask, no rope on K).
      impl: 'xla' | 'pallas' — prefill compute path.

    Returns:
      (out (B, S, d), updated cache or None)
    """
    q = layers.dense(params["wq"], x, compute_dtype=compute_dtype)
    kv_src = x if kv_override is None else kv_override
    k = layers.dense(params["wk"], kv_src, compute_dtype=compute_dtype)
    v = layers.dense(params["wv"], kv_src, compute_dtype=compute_dtype)

    if kv_override is None:
        if rope_kind == "rope":
            q = layers.apply_rope(q, positions, rope_theta)
            k = layers.apply_rope(k, positions, rope_theta)
        elif rope_kind == "mrope":
            assert mrope_positions is not None
            q = layers.apply_mrope(q, mrope_positions, rope_theta)
            k = layers.apply_mrope(k, mrope_positions, rope_theta)
        elif rope_kind != "none":
            raise ValueError(f"unknown rope kind {rope_kind!r}")

    scale = head_dim ** -0.5
    new_cache = None

    if cache is not None:
        # ---- decode: S == 1 query against the (rolling) cache -------------
        assert kv_override is None
        s_cache = cache["k"].shape[1]
        slot = cache["index"] % s_cache
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        pos_now = positions[0, -1]
        posc = jax.lax.dynamic_update_slice_in_dim(
            cache["positions"], pos_now[None].astype(jnp.int32), slot, axis=0)
        new_cache = {"k": kc, "v": vc, "positions": posc,
                     "index": cache["index"] + 1}
        scores = _gqa_scores(q, kc.astype(compute_dtype)) * scale
        valid = (posc >= 0) & (posc <= pos_now)
        if window > 0:
            valid &= posc > pos_now - window
        scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(probs, vc.astype(compute_dtype))
    else:
        # ---- prefill -------------------------------------------------------
        is_causal = causal and kv_override is None
        if impl == "pallas" and is_causal:
            from repro.kernels import ops as kops  # local import: optional path
            out = kops.flash_attention(q, k, v, window=window, scale=scale)
        else:
            kpos = positions if kv_override is None else \
                jnp.broadcast_to(jnp.arange(kv_src.shape[1])[None],
                                 (x.shape[0], kv_src.shape[1]))
            if chunked_prefill:
                out = _chunked_prefill(q, k, v, positions, kpos, scale=scale,
                                       window=window, causal=is_causal)
            else:
                # cfg.attn_chunked_prefill=False: dense one-block scores —
                # the only prefill the partially-auto 2-D region can lower
                out = _attend_block(q, k, v, positions, kpos, scale=scale,
                                    window=window, causal=is_causal)

    out = out.astype(compute_dtype)
    y = jnp.einsum("bshd,hdo->bso", out,
                   params["wo"]["w"].astype(compute_dtype))
    return y, new_cache
