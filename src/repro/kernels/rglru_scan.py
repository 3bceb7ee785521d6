"""Pallas TPU kernel for the RG-LRU gated linear recurrence.

    h_t = a_t ⊙ h_{t−1} + bx_t            (a, bx precomputed by the gates)

The XLA path uses ``associative_scan`` (O(log S) depth but ~2× the HBM
traffic from the scan tree's intermediates).  The kernel instead walks the
sequence in VMEM-resident tiles with the carry held in scratch:

  grid = (B, W_BLOCKS, S_BLOCKS)   — S innermost (sequential);
  scratch: h (1, BLOCK_W) f32, reset at s-block 0;
  per step: an (BLOCK_S, BLOCK_W) tile is loaded once, the recurrence runs
  as BLOCK_S vectorised VPU fma's over the W lanes, and the tile of h's is
  written back — one HBM read + one write per element, the bandwidth floor.

BLOCK_W is a lane multiple (≥128); BLOCK_S trades VMEM (2 tiles live) for
grid overhead.  The channel dim is embarrassingly parallel, which is what
lets the production sharding split W across the `model` axis with no
cross-device traffic (DESIGN §6: recurrence params are averaged by FedDec
like any other — the scan itself never leaves the device).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rglru_scan_pallas"]

DEFAULT_BLOCK_S = 256
DEFAULT_BLOCK_W = 256


def _rglru_kernel(a_ref, bx_ref, h_ref, carry):
    is_ = pl.program_id(2)

    @pl.when(is_ == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)

    a = a_ref[...].astype(jnp.float32)     # (BS, BW)
    bx = bx_ref[...].astype(jnp.float32)   # (BS, BW)
    bs = a.shape[0]

    def body(t, h):
        h = a[t] * h + bx[t]
        h_ref[t, :] = h.astype(h_ref.dtype)
        return h

    h0 = carry[0]
    h_last = jax.lax.fori_loop(0, bs, body, h0)
    carry[0, :] = h_last


@functools.partial(jax.jit, static_argnames=("block_s", "block_w",
                                             "interpret"))
def rglru_scan_pallas(a: jax.Array, bx: jax.Array, *,
                      block_s: int = DEFAULT_BLOCK_S,
                      block_w: int = DEFAULT_BLOCK_W,
                      interpret: bool = False):
    """Same contract as models.griffin.rglru_scan (h0 = 0).

    Args:
      a, bx: (B, S, W); S % block_s == 0 and W % block_w == 0 (the ops.py
        wrapper pads W).

    Returns:
      (h (B, S, W) f32, h_last (B, W) f32)
    """
    b, s, w = a.shape
    block_s = min(block_s, s)
    block_w = min(block_w, w)
    assert s % block_s == 0 and w % block_w == 0, (a.shape, block_s, block_w)
    grid = (b, w // block_w, s // block_s)
    h = pl.pallas_call(
        _rglru_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_s, block_w),
                         lambda ib, iw, is_: (ib, is_, iw)),
            pl.BlockSpec((None, block_s, block_w),
                         lambda ib, iw, is_: (ib, is_, iw)),
        ],
        out_specs=pl.BlockSpec((None, block_s, block_w),
                               lambda ib, iw, is_: (ib, is_, iw)),
        out_shape=jax.ShapeDtypeStruct((b, s, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(a, bx)
    return h, h[:, -1]
