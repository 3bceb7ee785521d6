"""Pallas TPU kernel for the FedDec mixing contraction  Y = W @ X.

This is the paper's own hot op (Algorithm 1, line 6) applied to the stacked
flat parameter matrix X ∈ (n_agents, D) with D up to ~10⁹.  Arithmetic
intensity is 2n FLOP per 4 bytes streamed — with n ≤ 64 that is far below
the TPU ridge point, i.e. the op is **HBM-bandwidth bound**; the kernel's
whole job is to stream X through VMEM exactly once at full bandwidth while
the (n, n) W stays VMEM-resident, and to fuse the doubly-stochastic mixing
matmul with the dtype cast (the XLA path materialises a f32 upcast of X
first — a 2× bandwidth tax).

Grid: 1-D over D tiles.  BlockSpecs:
  * W   (n, n)        — same block every step (index_map → (0, 0)),
  * X   (n, BLOCK_D)  — tile i,
  * Y   (n, BLOCK_D)  — tile i.

BLOCK_D is a multiple of 128 (lane width).  Row blocks span all n agents
(a block dim equal to the array dim needs no sublane alignment), and the
grid is ceil(D / BLOCK_D): the last tile is ragged and its out-of-bounds
columns are masked on write, so callers never copy the (n, D) buffer into
a padded one.  Every output column depends only on its own input column,
so the garbage read past D never reaches a kept element.  VMEM working set
per step = (2·n·BLOCK_D + n²)·4 B — with n=32, BLOCK_D=2048 that is
~0.5 MB, leaving headroom for double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gossip_mix_kernel", "gossip_mix_pallas",
           "gossip_mix_sparse_kernel", "gossip_mix_sparse_pallas",
           "gossip_mix_batched_kernel", "gossip_mix_batched_pallas",
           "gossip_mix_sparse_batched_kernel",
           "gossip_mix_sparse_batched_pallas", "ell_mix_tile", "ell_call"]

BLOCK_D = 2048

# f32 (n, block_d) VMEM scratch tiles of every ELL kernel (ell_mix_tile)
ELL_SCRATCH = 2

# In-kernel dots contract f32 operands at full f32 precision, as the XLA
# dense mix does (core.engine's precision=HIGHEST), so the Pallas and dense
# paths agree whatever Mosaic's default precision is.
HIGHEST = jax.lax.Precision.HIGHEST


def gossip_mix_kernel(w_ref, x_ref, y_ref):
    w = w_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    y_ref[...] = jnp.dot(
        w, x, precision=HIGHEST,
        preferred_element_type=jnp.float32).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gossip_mix_pallas(w: jax.Array, x: jax.Array, *, block_d: int = BLOCK_D,
                      interpret: bool = False) -> jax.Array:
    """y = w @ x with w (n, n), x (n, D); any n and D (ragged last tile)."""
    n, d = x.shape
    assert w.shape == (n, n), (w.shape, x.shape)
    grid = (pl.cdiv(d, block_d),)
    return pl.pallas_call(
        gossip_mix_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=interpret,
        name="gossip_mix",
    )(w, x)


# ---------------------------------------------------------------------------
# Batched (sweep-engine) variant: R independent runs, one kernel launch
# ---------------------------------------------------------------------------
#
# The sweep engine (repro.core.sweep) stacks R independent runs into one
# (R, n, D) buffer with per-run mixing matrices (R, n, n).  Mixing it run by
# run would reintroduce exactly the per-call dispatch the flat engine
# removed per leaf, so the batched kernel adds the run axis as the *leading
# grid dimension*: grid (R, D/BLOCK_D), with run r's W block VMEM-resident
# across that run's D tiles (index_map (r, i) → (r, 0, 0)).  Per grid step
# the work and VMEM footprint are identical to the single-run kernel — the
# batch multiplies the number of grid steps, not the working set — and the
# per-run arithmetic is the same (n, n) @ (n, BLOCK_D) dot, so each run's
# output is bit-identical to the single-run kernel on its slice.


def gossip_mix_batched_kernel(w_ref, x_ref, y_ref):
    w = w_ref[0].astype(jnp.float32)
    x = x_ref[0].astype(jnp.float32)
    y_ref[0] = jnp.dot(
        w, x, precision=HIGHEST,
        preferred_element_type=jnp.float32).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gossip_mix_batched_pallas(w: jax.Array, x: jax.Array, *,
                              block_d: int = BLOCK_D,
                              interpret: bool = False) -> jax.Array:
    """y[r] = w[r] @ x[r] with w (R, n, n), x (R, n, D)."""
    r, n, d = x.shape
    assert w.shape == (r, n, n), (w.shape, x.shape)
    grid = (r, pl.cdiv(d, block_d))
    return pl.pallas_call(
        gossip_mix_batched_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n, n), lambda r_, i: (r_, 0, 0)),
            pl.BlockSpec((1, n, block_d), lambda r_, i: (r_, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, n, block_d), lambda r_, i: (r_, 0, i)),
        out_shape=jax.ShapeDtypeStruct((r, n, d), x.dtype),
        interpret=interpret,
        name="gossip_mix_batched",
    )(w, x)


# ---------------------------------------------------------------------------
# Edge-blocked sparse variant:  y_i = W_ii x_i + Σ_{(i,j)∈E} W_ij x_j
# ---------------------------------------------------------------------------
#
# For sparse graphs the dense contraction wastes n/deg of its FLOPs and W
# reads on structural zeros.  This kernel keeps the dense variant's 1-D grid
# over D tiles (X still streams through VMEM exactly once), but replaces the
# (n, n) matmul with an accumulation over the graph's static directed edge
# list in ELL layout: per agent a (max_deg,)-padded neighbour index row
# (padded slots point at the agent itself with weight 0).  Per tile the work
# is O(max_deg·n·BLOCK_D) instead of O(n²·BLOCK_D) — on a ring (max_deg=2)
# that is the n/2× FLOP cut that makes n=256 viable.  The weights are read
# from the sampled W per edge, so random link failures (zeroed entries) need
# no re-indexing.
#
# The neighbour table is scalar-prefetched into SMEM (flattened row-major),
# and each neighbour row is read with a dynamic one-row sublane slice of an
# f32 VMEM copy of the tile: Mosaic lowers neither a vector gather
# (``jnp.take``) nor a lane slice at a dynamic index.  Slot k's gathered
# rows land in a second VMEM scratch, so the accumulation is the same
# whole-tile ``acc + wv[:, k] · rows_k`` sequence as a dense gather would
# give, in the same order.


def ell_mix_tile(nbr_ref, base, wv, wd, src32, sbuf, gbuf):
    """wd·src + Σ_k wv[:, k]·src[nbr[:, k]] for one (n, bd) f32 tile.

    ``nbr_ref`` is the flattened SMEM neighbour table and ``base`` the
    offset of this tile's (n, max_deg) block in it; ``wv`` (n, max_deg) and
    ``wd`` (n,) are the edge and diagonal weights; ``sbuf``/``gbuf`` are
    (n, bd) f32 VMEM scratch buffers.  Shared by every ELL kernel here and
    in kernels/update_mix.py.
    """
    n, max_deg = wv.shape
    sbuf[...] = src32
    acc = wd.astype(jnp.float32).reshape(-1, 1) * src32
    for k in range(max_deg):
        def gather(i, carry, k=k):
            j = nbr_ref[base + i * max_deg + k]
            gbuf[pl.ds(i, 1), :] = sbuf[pl.ds(j, 1), :]
            return carry

        jax.lax.fori_loop(0, n, gather, 0)
        acc = acc + wv[:, k:k + 1].astype(jnp.float32) * gbuf[...]
    return acc


def ell_call(kernel, nbr, n_tab, grid, in_specs, out_specs, out_shape,
             block_d, interpret, *, name):
    """pallas_call ``name`` with the (…, n, max_deg) neighbour table ``nbr``
    scalar-prefetched (flattened) and ELL_SCRATCH (n_tab, block_d) f32
    VMEM buffers; ``in_specs``/``out_specs`` index maps take the grid
    indices only (the prefetched table is appended here)."""
    def lift(spec):
        return pl.BlockSpec(spec.block_shape,
                            lambda *a, f=spec.index_map: f(*a[:-1]))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid,
        in_specs=[lift(s) for s in in_specs],
        out_specs=jax.tree.map(lift, out_specs,
                               is_leaf=lambda s: isinstance(s, pl.BlockSpec)),
        scratch_shapes=[pltpu.VMEM((n_tab, block_d), jnp.float32)
                        for _ in range(ELL_SCRATCH)])

    def call(*args):
        return pl.pallas_call(kernel, grid_spec=grid_spec,
                              out_shape=out_shape, interpret=interpret,
                              name=name)(
            nbr.reshape(-1).astype(jnp.int32), *args)
    return call


def gossip_mix_sparse_kernel(nbr_ref, wv_ref, wd_ref, x_ref, y_ref, sbuf,
                             gbuf):
    acc = ell_mix_tile(nbr_ref, 0, wv_ref[...], wd_ref[...],
                       x_ref[...].astype(jnp.float32), sbuf, gbuf)
    y_ref[...] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gossip_mix_sparse_pallas(nbr: jax.Array, wv: jax.Array, wd: jax.Array,
                             x: jax.Array, *, block_d: int = BLOCK_D,
                             interpret: bool = False) -> jax.Array:
    """Edge-blocked sparse mix.

    Args:
      nbr: (n, max_deg) int32 ELL neighbour indices (self-index on padding).
      wv:  (n, max_deg) edge weights W[i, nbr[i, k]] (0 on padding slots).
      wd:  (n,) diagonal weights W_ii.
      x:   (n, d) stacked flats.
    """
    n, d = x.shape
    assert nbr.shape == wv.shape and nbr.shape[0] == n, (nbr.shape, x.shape)
    max_deg = nbr.shape[1]
    return ell_call(
        gossip_mix_sparse_kernel, nbr, n, (pl.cdiv(d, block_d),),
        in_specs=[pl.BlockSpec((n, max_deg), lambda i: (0, 0)),
                  pl.BlockSpec((n,), lambda i: (0,)),
                  pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        block_d=block_d, interpret=interpret,
        name="gossip_mix_sparse")(wv, wd, x)


def gossip_mix_sparse_batched_kernel(nbr_ref, wv_ref, wd_ref, x_ref, y_ref,
                                     sbuf, gbuf):
    n, max_deg = wv_ref.shape[1:]
    acc = ell_mix_tile(nbr_ref, pl.program_id(0) * (n * max_deg),
                       wv_ref[0], wd_ref[0], x_ref[0].astype(jnp.float32),
                       sbuf, gbuf)
    y_ref[0] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gossip_mix_sparse_batched_pallas(nbr: jax.Array, wv: jax.Array,
                                     wd: jax.Array, x: jax.Array, *,
                                     block_d: int = BLOCK_D,
                                     interpret: bool = False) -> jax.Array:
    """Edge-blocked sparse mix over R runs in one launch (sweep engine).

    Per-run topologies may differ: each run carries its own ELL table,
    padded to the lattice-wide max degree (padding points at the row's own
    agent with weight 0, contributing exactly +0.0).  Grid (R, D/block_d):
    run r's (n, max_deg) tables stay VMEM-resident across its D tiles.

    Args:
      nbr: (R, n, max_deg) int32 per-run ELL neighbour indices.
      wv:  (R, n, max_deg) edge weights W[r, i, nbr[r, i, k]] (0 on padding).
      wd:  (R, n) diagonal weights W_ii per run.
      x:   (R, n, d) stacked run buffers.
    """
    r, n, d = x.shape
    assert nbr.shape == wv.shape and nbr.shape[:2] == (r, n), \
        (nbr.shape, x.shape)
    max_deg = nbr.shape[2]
    return ell_call(
        gossip_mix_sparse_batched_kernel, nbr, n, (r, pl.cdiv(d, block_d)),
        in_specs=[pl.BlockSpec((1, n, max_deg), lambda r_, i: (r_, 0, 0)),
                  pl.BlockSpec((1, n, 1), lambda r_, i: (r_, 0, 0)),
                  pl.BlockSpec((1, n, block_d), lambda r_, i: (r_, 0, i))],
        out_specs=pl.BlockSpec((1, n, block_d), lambda r_, i: (r_, 0, i)),
        out_shape=jax.ShapeDtypeStruct((r, n, d), x.dtype),
        block_d=block_d, interpret=interpret,
        name="gossip_mix_sparse_batched")(wv, wd.reshape(r, n, 1), x)
