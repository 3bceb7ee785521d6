"""Pallas kernels fusing int8 gossip compression with the mixing contraction.

The compressed-gossip pipeline (repro.core.compress) around Algorithm 1
line 6 is, per step:

    quantize  u → (q, scale)        stochastic-rounding int8, per-row scale
    mix       y = W s + diag(W)(p − s),   s = q · scale
    residual  e' = u − s

Composed as separate XLA ops this materialises the dequantized f32 ``s``
(one extra write+read of the full (n, D) buffer) and streams ``u`` twice.
These kernels fuse the stages into single streaming passes with W resident
in VMEM, exactly like kernels/gossip_mix.py's dense kernel (same 1-D grid
over D tiles, same BlockSpecs):

  * ``quant_mix_kernel``   — send side: reads u, noise, p once, emits both
    the mixed y and the int8 q (for the residual e' = u − q·scale) in one
    pass; the f32 s never touches HBM.
  * ``dequant_mix_kernel`` — receive side: mixes directly from the int8
    payload (q at 1 byte/element + per-row scales), fusing the dequantize
    into the contraction — the unfused XLA path writes/reads a 4-byte f32
    s first (see analysis.compress_row_bytes for the byte model).

Rounding noise is streamed in as a U[0,1) input tile rather than generated
with the TPU PRNG primitives: the same kernel body then runs bit-identically
under CPU interpret mode (this container / CI) and on device, and the noise
matches the XLA encode path exactly — tests/test_compress.py asserts q/y
equality against repro.core.compress.Int8Compressor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gossip_mix import HIGHEST

__all__ = ["quant_mix_kernel", "quant_mix_pallas",
           "dequant_mix_kernel", "dequant_mix_pallas"]

BLOCK_D = 2048


def quant_mix_kernel(w_ref, diag_ref, scale_ref, u_ref, noise_ref, p_ref,
                     y_ref, q_ref):
    w = w_ref[...].astype(jnp.float32)                 # (n, n)
    scale = scale_ref[...].astype(jnp.float32)         # (n,)
    u = u_ref[...].astype(jnp.float32)                 # (n, bd)
    q = jnp.clip(jnp.floor(u / scale[:, None] + noise_ref[...]),
                 -127.0, 127.0)
    s = q * scale[:, None]
    p = p_ref[...].astype(jnp.float32)
    y = jnp.dot(w, s, precision=HIGHEST,
                preferred_element_type=jnp.float32) \
        + diag_ref[...].astype(jnp.float32)[:, None] * (p - s)
    y_ref[...] = y.astype(y_ref.dtype)
    q_ref[...] = q.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def quant_mix_pallas(w: jax.Array, diag: jax.Array, scale: jax.Array,
                     u: jax.Array, noise: jax.Array, p: jax.Array, *,
                     block_d: int = BLOCK_D,
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """(y, q) = fused stochastic-int8 quantize + mix + EF correction.

    w (n, n), diag = W_ii (n,), scale (n,), u/noise/p (n, D).
    """
    n, d = u.shape
    assert w.shape == (n, n), (w.shape, u.shape)
    assert noise.shape == u.shape == p.shape, (noise.shape, u.shape, p.shape)
    grid = (pl.cdiv(d, block_d),)
    row_spec = pl.BlockSpec((n,), lambda i: (0,))
    tile_spec = pl.BlockSpec((n, block_d), lambda i: (0, i))
    return pl.pallas_call(
        quant_mix_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n, n), lambda i: (0, 0)),
                  row_spec, row_spec, tile_spec, tile_spec, tile_spec],
        out_specs=(tile_spec, tile_spec),
        out_shape=(jax.ShapeDtypeStruct((n, d), p.dtype),
                   jax.ShapeDtypeStruct((n, d), jnp.int8)),
        interpret=interpret,
        name="quant_mix",
    )(w, diag, scale, u, noise, p)


def dequant_mix_kernel(w_ref, diag_ref, scale_ref, q_ref, p_ref, y_ref):
    w = w_ref[...].astype(jnp.float32)
    s = q_ref[...].astype(jnp.float32) \
        * scale_ref[...].astype(jnp.float32)[:, None]
    p = p_ref[...].astype(jnp.float32)
    y = jnp.dot(w, s, precision=HIGHEST,
                preferred_element_type=jnp.float32) \
        + diag_ref[...].astype(jnp.float32)[:, None] * (p - s)
    y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def dequant_mix_pallas(w: jax.Array, diag: jax.Array, scale: jax.Array,
                       q: jax.Array, p: jax.Array, *,
                       block_d: int = BLOCK_D,
                       interpret: bool = False) -> jax.Array:
    """y = W (q·scale) + diag·(p − q·scale), streaming q at 1 B/element."""
    n, d = q.shape
    assert w.shape == (n, n), (w.shape, q.shape)
    grid = (pl.cdiv(d, block_d),)
    row_spec = pl.BlockSpec((n,), lambda i: (0,))
    tile_spec = pl.BlockSpec((n, block_d), lambda i: (0, i))
    return pl.pallas_call(
        dequant_mix_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n, n), lambda i: (0, 0)),
                  row_spec, row_spec, tile_spec, tile_spec],
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), p.dtype),
        interpret=interpret,
        name="dequant_mix",
    )(w, diag, scale, q, p)
