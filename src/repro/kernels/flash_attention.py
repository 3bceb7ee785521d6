"""Pallas TPU flash attention (causal / sliding-window, GQA).

Online-softmax tiling (Dao et al., adapted to the TPU memory hierarchy):

  grid = (B, KV_HEADS, GROUP, NUM_Q_BLOCKS)   — embarrassingly parallel
  per program: one (BLOCK_Q, head_dim) query tile, streamed against
  (BLOCK_K, head_dim) key/value tiles with running (max, denom, acc) carried
  in f32 registers.  Causality and the sliding window bound the K loop:
  blocks entirely outside [q_hi − window, q_hi] are never visited — this is
  the structural win for gemma3/recurrentgemma local layers (window ≪ S ⇒
  O(S·window) instead of O(S²)).

BlockSpec geometry: Q/O tiles are (1, 1, 1, BLOCK_Q, head_dim) over a
(B, KV, G, S, hd) view — BLOCK_Q a multiple of the 8-sublane f32 tile and
head_dim ∈ {64, 128, 256} a lane multiple.  K/V are delivered whole per
(b, kv) program (S ≤ ~8k fits VMEM at bf16; longer sequences would stream
via async HBM copies — noted, not needed for the validated shapes).

Numerics match ref.flash_attention_ref to ~1e-2 (bf16) / 1e-5 (f32);
interpret=True executes the same kernel body on CPU for the test sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention_pallas"]

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, window: int,
                  block_k: int, seq_len: int):
    # q_ref: (BLOCK_Q, hd); k_ref/v_ref: (S, hd); o_ref: (BLOCK_Q, hd)
    block_q, hd = q_ref.shape
    iq = pl.program_id(3)
    q0 = iq * block_q
    q = q_ref[...].astype(jnp.float32) * scale

    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(ik, carry):
        m_prev, l_prev, acc = carry
        k0 = ik * block_k
        k = k_ref[pl.ds(k0, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(k0, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 1)
        mask = kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=1)
        acc = acc * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    # K-loop bounds: causal upper bound, window lower bound
    q_hi = q0 + block_q - 1
    hi = jnp.minimum((q_hi // block_k) + 1, seq_len // block_k)
    if window > 0:
        lo = jnp.maximum((q0 - window + 1) // block_k, 0)
    else:
        lo = 0

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    o_ref[...] = (acc / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           window: int = 0, scale: float | None = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = False) -> jax.Array:
    """Causal (optionally windowed) GQA flash attention.

    Args:
      q: (B, S, H, hd); k, v: (B, S, KV, hd) with H % KV == 0.
      window: sliding-window size (0 ⇒ full causal).

    Returns:
      (B, S, H, hd) attention output in q.dtype.
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    if scale is None:
        scale = hd ** -0.5
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)

    # (B, S, H, hd) → (B, KV, G, S, hd) so each program owns one (b, kv, g)
    qv = q.reshape(b, s, kv, g, hd).transpose(0, 2, 3, 1, 4)
    kvw = k.transpose(0, 2, 1, 3)  # (B, KV, S, hd)
    vvw = v.transpose(0, 2, 1, 3)

    grid = (b, kv, g, s // block_q)
    kernel = functools.partial(_flash_kernel, scale=scale, window=window,
                               block_k=block_k, seq_len=s)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, None, block_q, hd),
                         lambda ib, ik, ig, iq: (ib, ik, ig, iq, 0)),
            pl.BlockSpec((None, None, s, hd),
                         lambda ib, ik, ig, iq: (ib, ik, 0, 0)),
            pl.BlockSpec((None, None, s, hd),
                         lambda ib, ik, ig, iq: (ib, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, None, block_q, hd),
                               lambda ib, ik, ig, iq: (ib, ik, ig, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, s, hd), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qv, kvw, vvw)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
