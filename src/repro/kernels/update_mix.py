"""Pallas TPU kernels fusing the FedDec local update with the gossip mix.

Algorithm 1's hot pair — line 5 (local SGD step) then line 6 (peer
averaging) — is memory-bandwidth bound: the unfused engines stream the
flat (n, D) buffer once to apply the update and again to mix, i.e. five
full-buffer passes per step for sgd (read x, read g, write p; read p,
write y) where three suffice (read x, read g, write y).  These kernels
compute the post-update iterate *inside* the mixing tile so p never
touches HBM: per D tile, p = x − η·g (or the momentum step) is formed in
VMEM and immediately contracted against the VMEM-resident W.

Fusing is semantics-preserving because line 6 consumes only post-update
iterates: every x_j^{t+1/2} a tile needs is a function of that tile's own
x/g columns, so the tile recomputes all n rows' updates locally — O(n·bd)
extra FLOPs, zero extra HBM traffic.  The update arithmetic replicates
optim.optimizers bit for bit (sgd: x − η.astype(dtype)·g; momentum:
m' = β·m + g_f32, step β·m'+g when nesterov, x − η.astype(dtype)·step);
adamw's bias-corrected rescale needs the step counter and stays on the
unfused path (core.flat falls back).

Variants (each mirroring its gossip_mix.py counterpart's grid/BlockSpecs):
  * dense        — grid (D/bd,), W (n, n) VMEM-resident;
  * sparse ELL   — same grid, the scalar-prefetched (n, max_deg) edge
    table walked by kernels.gossip_mix.ell_mix_tile;
  * batched      — leading run axis, grid (R, D/bd) (sweep engine);
  * ef_*         — the codec-active receive side: the update and the
    whole-row encode (int8 scales are full-row reductions — they cannot
    live in a D tile) stay on XLA, and the kernel fuses mix + the
    diag(W)·(p − s) EF correction + the u − s residual into one pass
    over (p, s, u) instead of three.

η rides in as a (1, 1) (or (R, 1)) f32 array so the same compiled kernel
serves every step of the diminishing-stepsize schedule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gossip_mix import HIGHEST, ell_call, ell_mix_tile

__all__ = [
    "update_mix_pallas", "update_mix_batched_pallas",
    "update_mix_sparse_pallas", "update_mix_sparse_batched_pallas",
    "ef_mix_pallas", "ef_mix_batched_pallas",
    "ef_mix_sparse_pallas", "ef_mix_sparse_batched_pallas",
]


def _local_step(x, g, m, eta, beta, nesterov):
    """p (native dtype) and new momentum (f32) — optim.optimizers numerics.

    ``beta is None`` selects plain sgd (the paper's line 5); otherwise the
    heavy-ball / nesterov step with the f32 momentum slot.
    """
    if beta is None:
        return x - eta.astype(x.dtype) * g, None
    g32 = g.astype(jnp.float32)
    new_m = beta * m + g32
    step = beta * new_m + g32 if nesterov else new_m
    return x - eta.astype(x.dtype) * step.astype(x.dtype), new_m


def _dense_mix(w, p):
    return jnp.dot(w.astype(jnp.float32), p.astype(jnp.float32),
                   precision=HIGHEST, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Dense fused update + mix
# ---------------------------------------------------------------------------


def _make_dense_kernel(beta, nesterov):
    if beta is None:
        def kernel(w_ref, x_ref, g_ref, eta_ref, y_ref):
            p, _ = _local_step(x_ref[...], g_ref[...], None,
                               eta_ref[0, 0], None, False)
            y_ref[...] = _dense_mix(w_ref[...], p).astype(y_ref.dtype)
        return kernel

    def kernel(w_ref, x_ref, g_ref, m_ref, eta_ref, y_ref, m_out_ref):
        p, new_m = _local_step(x_ref[...], g_ref[...], m_ref[...],
                               eta_ref[0, 0], beta, nesterov)
        m_out_ref[...] = new_m
        y_ref[...] = _dense_mix(w_ref[...], p).astype(y_ref.dtype)
    return kernel


@functools.partial(jax.jit, static_argnames=("beta", "nesterov", "block_d",
                                             "interpret"))
def update_mix_pallas(w, x, g, eta, m=None, *, beta=None, nesterov=False,
                      block_d: int, interpret: bool = False):
    """y = W @ (x − η·g) (sgd) or the momentum step; one pass over x/g.

    w (n, n), x/g (n, D), eta (1, 1) f32, m (n, D) f32 when ``beta`` is
    set.  Returns y (x.dtype), or (y, new_m) under momentum.
    """
    n, d = x.shape
    assert w.shape == (n, n), (w.shape, x.shape)
    grid = (pl.cdiv(d, block_d),)
    w_spec = pl.BlockSpec((n, n), lambda i: (0, 0))
    nd_spec = pl.BlockSpec((n, block_d), lambda i: (0, i))
    eta_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    kernel = _make_dense_kernel(beta, nesterov)
    if beta is None:
        return pl.pallas_call(
            kernel, grid=grid,
            in_specs=[w_spec, nd_spec, nd_spec, eta_spec],
            out_specs=nd_spec,
            out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
            interpret=interpret,
            name="update_mix",
        )(w, x, g, eta)
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[w_spec, nd_spec, nd_spec, nd_spec, eta_spec],
        out_specs=(nd_spec, nd_spec),
        out_shape=(jax.ShapeDtypeStruct((n, d), x.dtype),
                   jax.ShapeDtypeStruct((n, d), jnp.float32)),
        interpret=interpret,
        name="update_mix",
    )(w, x, g, m, eta)


def _make_dense_batched_kernel(beta, nesterov):
    if beta is None:
        def kernel(w_ref, x_ref, g_ref, eta_ref, y_ref):
            p, _ = _local_step(x_ref[0], g_ref[0], None,
                               eta_ref[0], None, False)
            y_ref[0] = _dense_mix(w_ref[0], p).astype(y_ref.dtype)
        return kernel

    def kernel(w_ref, x_ref, g_ref, m_ref, eta_ref, y_ref, m_out_ref):
        p, new_m = _local_step(x_ref[0], g_ref[0], m_ref[0],
                               eta_ref[0], beta, nesterov)
        m_out_ref[0] = new_m
        y_ref[0] = _dense_mix(w_ref[0], p).astype(y_ref.dtype)
    return kernel


@functools.partial(jax.jit, static_argnames=("beta", "nesterov", "block_d",
                                             "interpret"))
def update_mix_batched_pallas(w, x, g, eta, m=None, *, beta=None,
                              nesterov=False, block_d: int,
                              interpret: bool = False):
    """Batched fused update + mix over R runs: grid (R, D/block_d).

    w (R, n, n), x/g (R, n, D), eta (R, 1) f32 (per-run η_t — the sweep
    lattice shares the schedule but the shape keeps the kernel general),
    m (R, n, D) f32 under momentum.  Per-run vectors ride as (R, ·, 1)
    blocks: a block's last two dims must tile (8, 128) or span the array.
    """
    r, n, d = x.shape
    assert w.shape == (r, n, n), (w.shape, x.shape)
    grid = (r, pl.cdiv(d, block_d))
    w_spec = pl.BlockSpec((1, n, n), lambda r_, i: (r_, 0, 0))
    nd_spec = pl.BlockSpec((1, n, block_d), lambda r_, i: (r_, 0, i))
    eta_spec = pl.BlockSpec((1, 1, 1), lambda r_, i: (r_, 0, 0))
    eta = eta.reshape(r, 1, 1)
    kernel = _make_dense_batched_kernel(beta, nesterov)
    if beta is None:
        return pl.pallas_call(
            kernel, grid=grid,
            in_specs=[w_spec, nd_spec, nd_spec, eta_spec],
            out_specs=nd_spec,
            out_shape=jax.ShapeDtypeStruct((r, n, d), x.dtype),
            interpret=interpret,
            name="update_mix_batched",
        )(w, x, g, eta)
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[w_spec, nd_spec, nd_spec, nd_spec, eta_spec],
        out_specs=(nd_spec, nd_spec),
        out_shape=(jax.ShapeDtypeStruct((r, n, d), x.dtype),
                   jax.ShapeDtypeStruct((r, n, d), jnp.float32)),
        interpret=interpret,
        name="update_mix_batched",
    )(w, x, g, m, eta)


# ---------------------------------------------------------------------------
# Sparse ELL fused update + mix
# ---------------------------------------------------------------------------


def _make_sparse_kernel(beta, nesterov, batched):
    """Fused update + ELL mix; ``batched`` blocks carry a leading run dim
    (and the run's table offset comes from the grid's first index)."""
    lead = 0 if batched else ...

    def eta_of(eta_ref):
        return eta_ref[0] if batched else eta_ref[0, 0]

    def mix(nbr_ref, wv_ref, wd_ref, p, sbuf, gbuf):
        wv, wd = wv_ref[lead], wd_ref[lead]
        base = pl.program_id(0) * wv.size if batched else 0
        return ell_mix_tile(nbr_ref, base, wv, wd, p.astype(jnp.float32),
                            sbuf, gbuf)

    if beta is None:
        def kernel(nbr_ref, wv_ref, wd_ref, x_ref, g_ref, eta_ref, y_ref,
                   sbuf, gbuf):
            p, _ = _local_step(x_ref[lead], g_ref[lead], None,
                               eta_of(eta_ref), None, False)
            acc = mix(nbr_ref, wv_ref, wd_ref, p, sbuf, gbuf)
            y_ref[lead] = acc.astype(y_ref.dtype)
        return kernel

    def kernel(nbr_ref, wv_ref, wd_ref, x_ref, g_ref, m_ref, eta_ref,
               y_ref, m_out_ref, sbuf, gbuf):
        p, new_m = _local_step(x_ref[lead], g_ref[lead], m_ref[lead],
                               eta_of(eta_ref), beta, nesterov)
        m_out_ref[lead] = new_m
        acc = mix(nbr_ref, wv_ref, wd_ref, p, sbuf, gbuf)
        y_ref[lead] = acc.astype(y_ref.dtype)
    return kernel


@functools.partial(jax.jit, static_argnames=("beta", "nesterov", "block_d",
                                             "interpret"))
def update_mix_sparse_pallas(nbr, wv, wd, x, g, eta, m=None, *, beta=None,
                             nesterov=False, block_d: int,
                             interpret: bool = False):
    """Edge-blocked fused update + mix: every row's p is formed in-tile,
    then mixed over the static ELL table (padded slots: self-index,
    weight 0).  Same argument layout as gossip_mix_sparse_pallas plus
    (g, eta[, m])."""
    n, d = x.shape
    assert nbr.shape == wv.shape and nbr.shape[0] == n, (nbr.shape, x.shape)
    ell_spec = pl.BlockSpec((n, nbr.shape[1]), lambda i: (0, 0))
    wd_spec = pl.BlockSpec((n,), lambda i: (0,))
    nd_spec = pl.BlockSpec((n, block_d), lambda i: (0, i))
    eta_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    kernel = _make_sparse_kernel(beta, nesterov, batched=False)
    y_shape = jax.ShapeDtypeStruct((n, d), x.dtype)
    if beta is None:
        return ell_call(kernel, nbr, n, (pl.cdiv(d, block_d),),
                        [ell_spec, wd_spec, nd_spec, nd_spec, eta_spec],
                        nd_spec, y_shape, block_d, interpret,
                        name="update_mix_sparse")(
            wv, wd, x, g, eta)
    return ell_call(kernel, nbr, n, (pl.cdiv(d, block_d),),
                    [ell_spec, wd_spec, nd_spec, nd_spec, nd_spec, eta_spec],
                    (nd_spec, nd_spec),
                    (y_shape, jax.ShapeDtypeStruct((n, d), jnp.float32)),
                    block_d, interpret, name="update_mix_sparse")(
        wv, wd, x, g, m, eta)


@functools.partial(jax.jit, static_argnames=("beta", "nesterov", "block_d",
                                             "interpret"))
def update_mix_sparse_batched_pallas(nbr, wv, wd, x, g, eta, m=None, *,
                                     beta=None, nesterov=False,
                                     block_d: int,
                                     interpret: bool = False):
    """R-run fused update + ELL mix in one launch (sweep engine): per-run
    tables (R, n, max_deg), per-run η (R, 1); grid (R, D/block_d)."""
    r, n, d = x.shape
    assert nbr.shape == wv.shape and nbr.shape[:2] == (r, n), \
        (nbr.shape, x.shape)
    grid = (r, pl.cdiv(d, block_d))
    max_deg = nbr.shape[2]
    ell_spec = pl.BlockSpec((1, n, max_deg), lambda r_, i: (r_, 0, 0))
    wd_spec = pl.BlockSpec((1, n, 1), lambda r_, i: (r_, 0, 0))
    nd_spec = pl.BlockSpec((1, n, block_d), lambda r_, i: (r_, 0, i))
    eta_spec = pl.BlockSpec((1, 1, 1), lambda r_, i: (r_, 0, 0))
    wd, eta = wd.reshape(r, n, 1), eta.reshape(r, 1, 1)
    kernel = _make_sparse_kernel(beta, nesterov, batched=True)
    y_shape = jax.ShapeDtypeStruct((r, n, d), x.dtype)
    if beta is None:
        return ell_call(kernel, nbr, n, grid,
                        [ell_spec, wd_spec, nd_spec, nd_spec, eta_spec],
                        nd_spec, y_shape, block_d, interpret,
                        name="update_mix_sparse_batched")(
            wv, wd, x, g, eta)
    return ell_call(kernel, nbr, n, grid,
                    [ell_spec, wd_spec, nd_spec, nd_spec, nd_spec, eta_spec],
                    (nd_spec, nd_spec),
                    (y_shape, jax.ShapeDtypeStruct((r, n, d), jnp.float32)),
                    block_d, interpret, name="update_mix_sparse_batched")(
        wv, wd, x, g, m, eta)


# ---------------------------------------------------------------------------
# EF receive side: fused mix + diag correction + residual (codec active)
# ---------------------------------------------------------------------------


def ef_mix_kernel(w_ref, diag_ref, p_ref, s_ref, u_ref, y_ref, r_ref):
    p, s, u = p_ref[...], s_ref[...], u_ref[...]
    mix = _dense_mix(w_ref[...], s).astype(p.dtype)
    diag = diag_ref[...].astype(p.dtype).reshape(-1, 1)
    y_ref[...] = mix + diag * (p - s)
    r_ref[...] = u - s


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ef_mix_pallas(w, diag, p, s, u, *, block_d: int,
                  interpret: bool = False):
    """(y, new_res) = (W s + diag(W)·(p − s), u − s) in one pass.

    w (n, n), diag (n,) = diagonal(w) (precomputed — jnp.diagonal does not
    lower inside Mosaic), p/s/u (n, D).  Matches make_flat_ef_gossip's
    unfused composition term for term.
    """
    n, d = p.shape
    assert w.shape == (n, n) and diag.shape == (n,), (w.shape, diag.shape)
    grid = (pl.cdiv(d, block_d),)
    nd_spec = pl.BlockSpec((n, block_d), lambda i: (0, i))
    return pl.pallas_call(
        ef_mix_kernel, grid=grid,
        in_specs=[pl.BlockSpec((n, n), lambda i: (0, 0)),
                  pl.BlockSpec((n,), lambda i: (0,)),
                  nd_spec, nd_spec, nd_spec],
        out_specs=(nd_spec, nd_spec),
        out_shape=(jax.ShapeDtypeStruct((n, d), p.dtype),
                   jax.ShapeDtypeStruct((n, d), p.dtype)),
        interpret=interpret,
        name="ef_mix",
    )(w, diag, p, s, u)


def ef_mix_batched_kernel(w_ref, diag_ref, p_ref, s_ref, u_ref, y_ref,
                          r_ref):
    p, s, u = p_ref[0], s_ref[0], u_ref[0]
    mix = _dense_mix(w_ref[0], s).astype(p.dtype)
    diag = diag_ref[0].astype(p.dtype).reshape(-1, 1)
    y_ref[0] = mix + diag * (p - s)
    r_ref[0] = u - s


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ef_mix_batched_pallas(w, diag, p, s, u, *, block_d: int,
                          interpret: bool = False):
    """Batched EF mix: w (R, n, n), diag (R, n), p/s/u (R, n, D)."""
    r, n, d = p.shape
    assert w.shape == (r, n, n) and diag.shape == (r, n), \
        (w.shape, diag.shape)
    grid = (r, pl.cdiv(d, block_d))
    nd_spec = pl.BlockSpec((1, n, block_d), lambda r_, i: (r_, 0, i))
    diag = diag.reshape(r, n, 1)
    return pl.pallas_call(
        ef_mix_batched_kernel, grid=grid,
        in_specs=[pl.BlockSpec((1, n, n), lambda r_, i: (r_, 0, 0)),
                  pl.BlockSpec((1, n, 1), lambda r_, i: (r_, 0, 0)),
                  nd_spec, nd_spec, nd_spec],
        out_specs=(nd_spec, nd_spec),
        out_shape=(jax.ShapeDtypeStruct((r, n, d), p.dtype),
                   jax.ShapeDtypeStruct((r, n, d), p.dtype)),
        interpret=interpret,
        name="ef_mix_batched",
    )(w, diag, p, s, u)


def _make_ef_sparse_kernel(batched):
    lead = 0 if batched else ...

    def kernel(nbr_ref, wv_ref, wd_ref, p_ref, s_ref, u_ref, y_ref, r_ref,
               sbuf, gbuf):
        p, s, u = p_ref[lead], s_ref[lead], u_ref[lead]
        wv, wd = wv_ref[lead], wd_ref[lead]
        base = pl.program_id(0) * wv.size if batched else 0
        acc = ell_mix_tile(nbr_ref, base, wv, wd, s.astype(jnp.float32),
                           sbuf, gbuf)
        diag = wd.astype(p.dtype).reshape(-1, 1)
        y_ref[lead] = acc.astype(p.dtype) + diag * (p - s)
        r_ref[lead] = u - s
    return kernel


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ef_mix_sparse_pallas(nbr, wv, wd, p, s, u, *, block_d: int,
                         interpret: bool = False):
    """Sparse EF mix: ELL contraction of s plus the wd·(p − s) correction
    (wd doubles as diag(W)); same table layout as the uncompressed sparse
    kernels."""
    n, d = p.shape
    assert nbr.shape == wv.shape and nbr.shape[0] == n, (nbr.shape, p.shape)
    ell_spec = pl.BlockSpec((n, nbr.shape[1]), lambda i: (0, 0))
    nd_spec = pl.BlockSpec((n, block_d), lambda i: (0, i))
    out = jax.ShapeDtypeStruct((n, d), p.dtype)
    return ell_call(_make_ef_sparse_kernel(batched=False), nbr, n,
                    (pl.cdiv(d, block_d),),
                    [ell_spec, pl.BlockSpec((n,), lambda i: (0,)),
                     nd_spec, nd_spec, nd_spec],
                    (nd_spec, nd_spec), (out, out), block_d, interpret,
                    name="ef_mix_sparse")(
        wv, wd, p, s, u)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ef_mix_sparse_batched_pallas(nbr, wv, wd, p, s, u, *, block_d: int,
                                 interpret: bool = False):
    """R-run sparse EF mix: per-run ELL tables, grid (R, D/block_d)."""
    r, n, d = p.shape
    assert nbr.shape == wv.shape and nbr.shape[:2] == (r, n), \
        (nbr.shape, p.shape)
    max_deg = nbr.shape[2]
    ell_spec = pl.BlockSpec((1, n, max_deg), lambda r_, i: (r_, 0, 0))
    wd_spec = pl.BlockSpec((1, n, 1), lambda r_, i: (r_, 0, 0))
    nd_spec = pl.BlockSpec((1, n, block_d), lambda r_, i: (r_, 0, i))
    out = jax.ShapeDtypeStruct((r, n, d), p.dtype)
    wd = wd.reshape(r, n, 1)
    return ell_call(_make_ef_sparse_kernel(batched=True), nbr, n,
                    (r, pl.cdiv(d, block_d)),
                    [ell_spec, wd_spec, nd_spec, nd_spec, nd_spec],
                    (nd_spec, nd_spec), (out, out), block_d, interpret,
                    name="ef_mix_sparse_batched")(
        wv, wd, p, s, u)
