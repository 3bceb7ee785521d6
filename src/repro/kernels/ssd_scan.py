"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation of the Mamba2 GPU kernel (arXiv:2405.21060 §6): the GPU
version leans on warp-level shuffles for the intra-chunk scan; the TPU
version instead phrases the chunk-local work as three MXU matmuls —
(L×L)·(L×P) masked-decay attention, (P×L)·(L×N) state outer-product and
(L×N)·(N×P) state readout — with the *inter-chunk* recurrence carried in a
VMEM scratch accumulator across sequential grid steps (the same
persistent-scratch idiom a matmul uses for its K-loop accumulator).

  grid = (B, H, NUM_CHUNKS)   — NC is the innermost (sequential) dim;
  scratch: state (P, N) f32, reset at chunk 0 of every (b, h) program.

Inputs are pre-scaled by the wrapper (xl = Δ·x, la = Δ·A) so the kernel
streams exactly four tensors.  Block shapes: (L, P), (L,), (L, N), (L, N)
with L the chunk (multiple of 8 sublanes), P/N lane multiples (64/128) —
MXU-aligned at the assigned mamba2 dims (L=256, P=64, N=128).

VMEM per step ≈ L·(P+2N+1)·4 + L²·4 + P·N·4 ≈ 0.7 MB at those dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_pallas"]


def _ssd_kernel(xl_ref, la_ref, b_ref, c_ref, y_ref, state):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    xl = xl_ref[...].astype(jnp.float32)   # (L, P)
    la = la_ref[...].astype(jnp.float32)   # (L,)
    b = b_ref[...].astype(jnp.float32)     # (L, N)
    c = c_ref[...].astype(jnp.float32)     # (L, N)
    l = xl.shape[0]

    cum = jnp.cumsum(la)                   # (L,)
    total = cum[-1]

    # intra-chunk: masked-decay attention
    diff = cum[:, None] - cum[None, :]     # (L, L)
    mask = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    decay = jnp.where(mask, jnp.exp(diff), 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    y = jnp.dot(cb * decay, xl, preferred_element_type=jnp.float32)

    # inter-chunk: read out the carried state (before updating it)
    prev = state[...]                      # (P, N)
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        c, prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (L,N)·(P,N)ᵀ → (L,P)

    # state update: S ← exp(Σ la) S + Σ_j exp(total − cum_j) Δx_j ⊗ B_j
    rem = jnp.exp(total - cum)             # (L,)
    new_contrib = jax.lax.dot_general(
        xl, b * rem[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # (P, N)
    state[...] = prev * jnp.exp(total) + new_contrib

    y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                    c: jax.Array, *, chunk: int = 256,
                    interpret: bool = False):
    """SSD scan.  Same contract as models.ssm.ssd_chunked (zero init state).

    Args:
      x (B,S,H,P), dt (B,S,H), a (H,), b (B,S,N), c (B,S,N); S % chunk == 0.

    Returns:
      (y (B,S,H,P), None) — the final state is not materialised (training
      prefill does not need it; decode uses ssm.ssd_decode_step).
    """
    bs, s, h, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    f32 = jnp.float32
    xl = (x.astype(f32) * dt.astype(f32)[..., None])      # Δ·x
    la = dt.astype(f32) * a.astype(f32)                   # Δ·A (≤ 0)

    # layouts: (B, H, NC, L, ·) so (b, h) owns a contiguous chunk stream
    xl = xl.reshape(bs, nc, chunk, h, p).transpose(0, 3, 1, 2, 4)
    la = la.reshape(bs, nc, chunk, h).transpose(0, 3, 1, 2)
    bb = jnp.broadcast_to(b.astype(f32).reshape(bs, nc, chunk, n)[:, None],
                          (bs, h, nc, chunk, n))
    cc = jnp.broadcast_to(c.astype(f32).reshape(bs, nc, chunk, n)[:, None],
                          (bs, h, nc, chunk, n))

    grid = (bs, h, nc)
    y = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, None, chunk, p),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((None, None, None, chunk),
                         lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((None, None, None, chunk, n),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((None, None, None, chunk, n),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, None, chunk, p),
                               lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bs, h, nc, chunk, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(xl, la, bb, cc)
    return y.transpose(0, 2, 3, 1, 4).reshape(bs, s, h, p), None
