"""Public jit'd wrappers for the Pallas kernels.

Handles the host-side plumbing the kernels assume away: ``interpret=True``
whenever the backend is not a TPU (the kernel body still executes, in
Python, so CPU tests exercise the real kernel code), the D tile width, the
ELL tables of the sparse kernels, and pytree-level application for the
gossip op.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import compress_mix as _cm
from repro.kernels import flash_attention as _fa
from repro.kernels import gossip_mix as _gm
from repro.kernels import rglru_scan as _rg
from repro.kernels import ssd_scan as _ssd
from repro.kernels import update_mix as _um

__all__ = ["flash_attention", "gossip_mix", "gossip_mix_tree",
           "gossip_mix_batched", "make_sparse_gossip_pallas",
           "make_sparse_gossip_batched_pallas", "quant_mix", "dequant_mix",
           "update_mix", "update_mix_batched",
           "make_sparse_update_mix_pallas",
           "make_sparse_update_mix_batched_pallas",
           "ef_mix", "ef_mix_batched", "make_sparse_ef_mix_pallas",
           "make_sparse_ef_mix_batched_pallas", "autotune_block_d",
           "ssd_scan", "rglru_scan", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Pallas interpret mode: exactly when the backend is not a TPU.

    Off-TPU the kernel body still executes, in Python; on the chip every
    kernel is compiled by Mosaic — nothing can force it back to the
    interpreter there.
    """
    return not on_tpu()


# The D tile is as wide as a VMEM byte budget allows.  The streaming
# kernels are HBM-bound, and a Pallas TPU grid step costs a fixed ~0.35 µs
# whatever it moves, so a narrow tile pays for its grid, not its bytes.
# What the budget counts, per lane of the tile:
#   * each streamed (rows, block_d) operand, inputs and outputs, twice
#     (Pallas double-buffers them), its rows padded to the dtype's sublane
#     tile (8 rows at 4 B, 16 at 2 B, 32 at 1 B);
#   * the f32 (rows, block_d) scratch tiles of an ELL kernel;
#   * _TEMP_TILES more tiles of the widest streamed dtype for the in-tile
#     temporaries (p, the f32 casts, the dot's result).
# _VMEM_BUDGET stays well inside v5e's 16 MiB default scoped VMEM, so no
# kernel needs a vmem_limit_bytes.  _MAX_BLOCK_D comes from timing the
# fused SGD update+mix alone on a v5e chip, over a (4, 255,864,320) f32
# buffer: 60.6 ms a call at 2048 lanes (124,934 grid steps), 28.3 ms at
# 8192, 20.2 ms at 32,768 (74% of the HBM roofline) and 19.0 ms at 65,536.
# Past 32,768 lanes the grid costs under 3 ms a call, and the budget would
# count more than 16 MiB for those 4-row tiles.  REPRO_BLOCK_D overrides
# the width.
_VMEM_BUDGET = 12 << 20
_MAX_BLOCK_D = 32768
_TEMP_TILES = 3


def _tile_bytes(rows: int, dtype) -> int:
    """VMEM bytes per lane of a (rows, ·) tile of ``dtype``."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize
    return -(-rows // sublanes) * sublanes * itemsize


def block_d_vmem_bytes(block_d: int, rows: int, streams, *,
                       scratch: int = 0) -> int:
    """The VMEM bytes the budget counts for a ``block_d``-wide tile of
    ``rows`` rows: ``streams`` lists the dtype of every streamed operand
    (inputs and outputs), ``scratch`` the number of f32 scratch tiles."""
    widest = max(streams, key=lambda t: jnp.dtype(t).itemsize)
    per_lane = (2 * sum(_tile_bytes(rows, t) for t in streams)
                + _TEMP_TILES * _tile_bytes(rows, widest)
                + scratch * _tile_bytes(rows, jnp.float32))
    return block_d * per_lane


def autotune_block_d(rows: int, streams, *, scratch: int = 0) -> int:
    """The widest D tile, a multiple of 128 lanes up to _MAX_BLOCK_D, whose
    :func:`block_d_vmem_bytes` fits _VMEM_BUDGET; never under 128 lanes.

    Overridable via the ``REPRO_BLOCK_D`` env var or by passing
    ``block_d`` explicitly to any wrapper.
    """
    env = os.environ.get("REPRO_BLOCK_D")
    if env:
        return int(env)
    per_lane = block_d_vmem_bytes(1, rows, streams, scratch=scratch)
    return max(min(_VMEM_BUDGET // per_lane // 128 * 128, _MAX_BLOCK_D), 128)


def _resolve_block_d(block_d: int | None, x, streams: int, *,
                     scratch: int = 0, f32_streams: int = 0) -> int:
    """``block_d`` or the budget's width for the (…, rows, d) buffer ``x``:
    ``streams`` operands of x's dtype plus ``f32_streams`` f32 ones (a
    momentum slot), clamped to the lane-aligned cover of d."""
    *_, rows, d = x.shape
    if block_d is None:
        block_d = autotune_block_d(
            rows, (x.dtype,) * streams + (jnp.float32,) * f32_streams,
            scratch=scratch)
    return _clamp_block_d(block_d, d)


def flash_attention(q, k, v, *, window: int = 0, scale: float | None = None,
                    block_q: int = _fa.DEFAULT_BLOCK_Q,
                    block_k: int = _fa.DEFAULT_BLOCK_K):
    """Causal/windowed GQA flash attention (see flash_attention.py)."""
    return _fa.flash_attention_pallas(
        q, k, v, window=window, scale=scale, block_q=block_q,
        block_k=block_k, interpret=_interpret())


def _clamp_block_d(block_d: int, d: int) -> int:
    """Shrink the D tile to the smallest lane-aligned cover of ``d``.

    The 2-D engine hands the kernels (n_local, D/M) sub-blocks of the flat
    buffer; a full-width tile over those would compute mostly masked
    lanes.  The tile stays a multiple of the 128-lane width (f32 min tile
    is (8, 128)) and never grows past the requested ``block_d``, so
    large-D callers are untouched.
    """
    return max(min(block_d, -(-d // 128) * 128), 128)


def gossip_mix(w: jax.Array, x: jax.Array, *,
               block_d: int | None = None):
    """y = W @ X for (n, D) stacked flats, with the D tile sized from the
    VMEM budget when unset (clamped to the lane-aligned cover of D for
    narrow sub-blocks).  No padding copies: row blocks span all n agents
    and a ragged last D tile is masked by the kernel."""
    block_d = _resolve_block_d(block_d, x, 2)
    return _gm.gossip_mix_pallas(w, x, block_d=block_d,
                                 interpret=_interpret())


def gossip_mix_batched(w: jax.Array, x: jax.Array, *,
                       block_d: int | None = None):
    """y[r] = W[r] @ X[r] for (R, n, D) stacked run buffers (sweep engine).

    One kernel launch for the whole run lattice — grid (R, D/block_d) —
    instead of R dispatches of the single-run kernel; every run's slice is
    bit-identical to the single-run kernel's output.
    """
    block_d = _resolve_block_d(block_d, x, 2)
    return _gm.gossip_mix_batched_pallas(w, x, block_d=block_d,
                                         interpret=_interpret())


def gossip_mix_tree(w: jax.Array, stacked) -> object:
    """Apply the gossip kernel leaf-wise to a stacked (n, ...) pytree.

    Flattens every leaf to (n, D_leaf); the kernel streams each leaf once.
    Semantically identical to core.gossip.gossip_mix_dense.  The kernel
    upcasts W to f32 internally, so no per-leaf cast of W is needed here.
    """
    def mix(leaf):
        n = leaf.shape[0]
        flat = leaf.reshape(n, -1)
        return gossip_mix(w, flat).reshape(leaf.shape)
    return jax.tree.map(mix, stacked)


def _ell_tables(graphs):
    """Host-side ELL neighbour tables of an R-run lattice, closed over by
    every sparse wrapper: (nbr, valid) (R, n, max_deg), padded slots
    pointing at the row's own agent (weight 0 at mix time)."""
    from repro.core import gossip as gossip_lib
    nbr, valid, _ = gossip_lib.stacked_ell_tables(graphs)
    return jnp.asarray(nbr), jnp.asarray(valid)


def _ell_weights(w, nbr, valid):
    """Live (wv, wd) edge/diagonal weights from the sampled W — (n, n) with
    (n, max_deg) tables, or (R, n, n) with (R, n, max_deg) tables."""
    wf = w.astype(jnp.float32)
    wv = jnp.where(valid, jnp.take_along_axis(wf, nbr, axis=-1), 0.0)
    return wv, jnp.diagonal(wf, axis1=-2, axis2=-1)


def make_sparse_gossip_pallas(graph, *, block_d: int | None = None):
    """Build the edge-blocked sparse Pallas mix for a static graph.

    Precomputes the ELL neighbour table (n, max_deg) host-side and closes
    over it: ``mix(w, x)`` reads the live edge weights from the sampled
    (n, n) W, so per-step link failures need no re-indexing.
    O(max_deg·n·d) work vs the dense kernel's O(n²·d); same single
    streaming pass over X.
    """
    nbr, valid = (t[0] for t in _ell_tables([graph]))

    def mix(w: jax.Array, x: jax.Array) -> jax.Array:
        assert x.shape[0] == graph.n, (x.shape, graph.n)
        bd = _resolve_block_d(block_d, x, 2, scratch=_gm.ELL_SCRATCH)
        wv, wd = _ell_weights(w, nbr, valid)
        return _gm.gossip_mix_sparse_pallas(nbr, wv, wd, x, block_d=bd,
                                            interpret=_interpret())

    return mix


def make_sparse_gossip_batched_pallas(graphs, *,
                                      block_d: int | None = None):
    """Build the edge-blocked sparse mix for an R-run topology lattice.

    Per-run ELL tables (n, max_deg) — max_deg is the lattice-wide maximum,
    shorter rows padded with weight-0 self-edges — are stacked to
    (R, n, max_deg) host-side and closed over; ``mix(w, x)`` with
    w (R, n, n), x (R, n, D) reads each run's live edge weights from its
    sampled W, so per-step link failures and per-run topologies need no
    re-indexing.  One kernel launch (grid (R, D/block_d)) covers the whole
    lattice.
    """
    nbr, valid = _ell_tables(graphs)

    def mix(w: jax.Array, x: jax.Array) -> jax.Array:
        assert x.shape[:2] == nbr.shape[:2], (x.shape, nbr.shape)
        bd = _resolve_block_d(block_d, x, 2, scratch=_gm.ELL_SCRATCH)
        wv, wd = _ell_weights(w, nbr, valid)
        return _gm.gossip_mix_sparse_batched_pallas(
            nbr, wv, wd, x, block_d=bd, interpret=_interpret())

    return mix


# ---------------------------------------------------------------------------
# Fused update + mix (kernels/update_mix.py) — one buffer pass per step
# ---------------------------------------------------------------------------


def _eta(eta, r=1):
    return jnp.asarray(eta, jnp.float32).reshape(r, 1)


def update_mix(w, x, g, eta, *, m=None, beta=None, nesterov=False,
               block_d: int | None = None):
    """y = W @ (x − η·g) (or the momentum step) in one pass over x/g.

    Returns y, or (y, new_m) when a momentum buffer ``m`` is passed with
    ``beta``.
    """
    bd = _resolve_block_d(block_d, x, 3, f32_streams=0 if m is None else 2)
    if m is None:
        return _um.update_mix_pallas(w, x, g, _eta(eta), block_d=bd,
                                     interpret=_interpret())
    assert beta is not None, "momentum buffer passed without beta"
    return _um.update_mix_pallas(w, x, g, _eta(eta), m, beta=beta,
                                 nesterov=nesterov, block_d=bd,
                                 interpret=_interpret())


def update_mix_batched(w, x, g, eta, *, m=None, beta=None, nesterov=False,
                       block_d: int | None = None):
    """Batched fused update + mix over (R, n, D) run buffers; eta (R,)."""
    bd = _resolve_block_d(block_d, x, 3, f32_streams=0 if m is None else 2)
    eta2 = _eta(eta, x.shape[0])
    if m is None:
        return _um.update_mix_batched_pallas(w, x, g, eta2, block_d=bd,
                                             interpret=_interpret())
    assert beta is not None, "momentum buffer passed without beta"
    return _um.update_mix_batched_pallas(w, x, g, eta2, m, beta=beta,
                                         nesterov=nesterov, block_d=bd,
                                         interpret=_interpret())


def _make_sparse_update_mix(graphs, batched, beta, nesterov, block_d):
    nbr, valid = _ell_tables(graphs)
    if not batched:
        nbr, valid = nbr[0], valid[0]
    kernel = _um.update_mix_sparse_batched_pallas if batched \
        else _um.update_mix_sparse_pallas

    def fused(w, x, g, eta, m=None):
        assert x.shape[:-1] == nbr.shape[:-1], (x.shape, nbr.shape)
        bd = _resolve_block_d(block_d, x, 3, scratch=_gm.ELL_SCRATCH,
                              f32_streams=0 if m is None else 2)
        wv, wd = _ell_weights(w, nbr, valid)
        eta2 = _eta(eta, x.shape[0] if batched else 1)
        if m is None:
            return kernel(nbr, wv, wd, x, g, eta2, block_d=bd,
                          interpret=_interpret())
        assert beta is not None, "momentum buffer passed without beta"
        return kernel(nbr, wv, wd, x, g, eta2, m, beta=beta,
                      nesterov=nesterov, block_d=bd, interpret=_interpret())

    return fused


def make_sparse_update_mix_pallas(graph, *, beta=None, nesterov=False,
                                  block_d: int | None = None):
    """Build the edge-blocked fused update + mix for a static graph.

    Same ELL precompute as :func:`make_sparse_gossip_pallas`; the closure
    ``fused(w, x, g, eta, m=None)`` reads live edge weights from the
    sampled W each step.
    """
    return _make_sparse_update_mix([graph], False, beta, nesterov, block_d)


def make_sparse_update_mix_batched_pallas(graphs, *, beta=None,
                                          nesterov=False,
                                          block_d: int | None = None):
    """R-run fused update + ELL mix (sweep engine); per-run topologies."""
    return _make_sparse_update_mix(graphs, True, beta, nesterov, block_d)


def ef_mix(w, p, s, u, *, block_d: int | None = None):
    """Fused EF receive side: (W s + diag(W)·(p − s), u − s) in one pass.

    The encode (whole-row reductions) stays on the shared XLA codec; this
    replaces the mix + correction + residual triple of passes.
    """
    bd = _resolve_block_d(block_d, p, 5)
    return _um.ef_mix_pallas(w, jnp.diagonal(w), p, s, u, block_d=bd,
                             interpret=_interpret())


def ef_mix_batched(w, p, s, u, *, block_d: int | None = None):
    """Batched fused EF receive side over (R, n, D) run buffers."""
    bd = _resolve_block_d(block_d, p, 5)
    return _um.ef_mix_batched_pallas(
        w, jnp.diagonal(w, axis1=1, axis2=2), p, s, u, block_d=bd,
        interpret=_interpret())


def _make_sparse_ef_mix(graphs, batched, block_d):
    nbr, valid = _ell_tables(graphs)
    if not batched:
        nbr, valid = nbr[0], valid[0]
    kernel = _um.ef_mix_sparse_batched_pallas if batched \
        else _um.ef_mix_sparse_pallas

    def ef(w, p, s, u):
        assert p.shape[:-1] == nbr.shape[:-1], (p.shape, nbr.shape)
        bd = _resolve_block_d(block_d, p, 5, scratch=_gm.ELL_SCRATCH)
        wv, wd = _ell_weights(w, nbr, valid)
        return kernel(nbr, wv, wd, p, s, u, block_d=bd,
                      interpret=_interpret())

    return ef


def make_sparse_ef_mix_pallas(graph, *, block_d: int | None = None):
    """Sparse fused EF receive side for a static graph: ``ef(w, p, s, u)``."""
    return _make_sparse_ef_mix([graph], False, block_d)


def make_sparse_ef_mix_batched_pallas(graphs, *,
                                      block_d: int | None = None):
    """R-run sparse fused EF receive side (sweep engine)."""
    return _make_sparse_ef_mix(graphs, True, block_d)


def quant_mix(w: jax.Array, u: jax.Array, noise: jax.Array, p: jax.Array,
              scale: jax.Array, *, block_d: int = _cm.BLOCK_D):
    """Fused int8 quantize → mix → EF-correct (send side).

    Returns (y, q): y = W·(q·scale) + diag(W)·(p − q·scale) with
    q = clip(⌊u/scale + noise⌋, ±127) — identical, element for element, to
    composing Int8Compressor.encode/decode with the dense mix (the noise
    and scale come from the caller, shared with the XLA path).
    """
    block_d = _clamp_block_d(block_d, u.shape[1])
    return _cm.quant_mix_pallas(w, jnp.diagonal(w),
                                scale.astype(jnp.float32), u, noise, p,
                                block_d=block_d, interpret=_interpret())


def dequant_mix(w: jax.Array, q: jax.Array, scale: jax.Array, p: jax.Array,
                *, block_d: int = _cm.BLOCK_D):
    """Fused int8 dequantize → mix (receive side): streams q at 1 B/elem."""
    block_d = _clamp_block_d(block_d, q.shape[1])
    return _cm.dequant_mix_pallas(w, jnp.diagonal(w),
                                  scale.astype(jnp.float32),
                                  q.astype(jnp.int8), p, block_d=block_d,
                                  interpret=_interpret())


def ssd_scan(x, dt, a, b, c, *, chunk: int = 256):
    """Mamba2 SSD chunked scan (see ssd_scan.py)."""
    return _ssd.ssd_scan_pallas(x, dt, a, b, c, chunk=chunk,
                                interpret=_interpret())


def rglru_scan(a, bx, *, block_s: int = _rg.DEFAULT_BLOCK_S,
               block_w: int = _rg.DEFAULT_BLOCK_W):
    """RG-LRU linear recurrence (see rglru_scan.py); pads S and W to tiles."""
    b, s, w = a.shape
    w_pad = (-w) % min(block_w, max(w, 1))
    s_pad = (-s) % min(block_s, max(s, 1))
    if w_pad or s_pad:
        # trailing padding only touches sliced-off outputs; the carry keeps
        # running through it (a=0 zeroes it), which is harmless
        a = jnp.pad(a, ((0, 0), (0, s_pad), (0, w_pad)))
        bx = jnp.pad(bx, ((0, 0), (0, s_pad), (0, w_pad)))
    h, h_last = _rg.rglru_scan_pallas(a, bx, block_s=block_s,
                                      block_w=block_w,
                                      interpret=_interpret())
    h = h[:, :s, :w]
    return h, h[:, -1]
