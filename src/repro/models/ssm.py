"""Mamba2 block — State Space Duality (SSD), arXiv:2405.21060.

The sequence mixer is the scalar-identity SSM

    S_t = exp(Δ_t A_h) S_{t-1} + Δ_t B_t ⊗ x_t,      y_t = C_tᵀ S_t + D_h x_t

computed with the paper's **chunked block decomposition** (§6): the sequence
is split into chunks of length L; the intra-chunk part is a masked-decay
attention-like matmul (MXU-friendly), the inter-chunk part is a short
recurrence over chunk states — O(S·L) instead of O(S²) with matmuls
dominating.  ``ssd_chunked`` is the canonical jnp implementation used as the
model's XLA path *and* as the Pallas kernel's oracle (kernels/ref.py
re-exports it); the Pallas kernel (kernels/ssd_scan.py) tiles the same
math over VMEM.

Decode keeps (conv ring buffer, SSM state) per layer — O(1) per token, which
is what makes ``long_500k`` native for this architecture.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models import layers

__all__ = ["init_mamba2", "mamba2_block", "init_mamba2_cache", "ssd_chunked",
           "ssd_decode_step", "SSD_SCOPE"]

# the named scope of the chunked scan in a block's forward (and so in its
# backward and recomputation): the trace reads the scan's device time by it
SSD_SCOPE = "ssm.ssd"


# ---------------------------------------------------------------------------
# SSD core (canonical jnp implementation — also the kernel oracle)
# ---------------------------------------------------------------------------


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int,
                initial_state: jax.Array | None = None):
    """Chunked SSD scan.

    Args:
      x:  (B, S, H, P)  inputs (already multiplied by nothing; Δ applied here).
      dt: (B, S, H)     positive step sizes (post-softplus).
      a:  (H,)          negative per-head decay rates (A = -exp(A_log)).
      b:  (B, S, N)     input projections (ngroups = 1, shared across heads).
      c:  (B, S, N)     output projections.
      chunk: chunk length L (must divide S).
      initial_state: (B, H, P, N) or None.

    Returns:
      y (B, S, H, P), final_state (B, H, P, N)
    """
    bs, s, h, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    f32 = jnp.float32
    xl = (x * dt[..., None]).astype(f32)           # Δx, (B,S,H,P)
    la = (dt.astype(f32) * a.astype(f32))          # log decay ΔA ≤ 0, (B,S,H)

    def r(t, shape):  # reshape seq into (nc, L)
        return t.reshape(shape)

    xl = r(xl, (bs, nc, chunk, h, p))
    la = r(la, (bs, nc, chunk, h))
    bc = r(b.astype(f32), (bs, nc, chunk, n))
    cc = r(c.astype(f32), (bs, nc, chunk, n))

    cum = jnp.cumsum(la, axis=2)                   # (B,NC,L,H) inclusive
    total = cum[:, :, -1, :]                       # (B,NC,H)

    # ---- intra-chunk: masked decay "attention" -----------------------------
    # decay[i,j] = exp(cum_i − cum_j) for i ≥ j (both inclusive cumsums ⇒
    # contribution of step j's input to step i's output).  Above the
    # diagonal diff is large and positive: it is masked to -inf before the
    # exp, which would overflow there, and whose backward would then
    # multiply inf by zero.
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # (B,NC,L,L,H)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(mask[None, None, :, :, None], diff, -jnp.inf))
    cb = jnp.einsum("bnid,bnjd->bnij", cc, bc)              # (B,NC,L,L)
    y_intra = jnp.einsum("bnij,bnijh,bnjhp->bnihp", cb, decay, xl)

    # ---- chunk states -------------------------------------------------------
    # state_c = Σ_j exp(total − cum_j) B_j ⊗ Δx_j   (B,NC,H,P,N)
    rem = jnp.exp(total[:, :, None, :] - cum)               # (B,NC,L,H)
    states = jnp.einsum("bnjh,bnjd,bnjhp->bnhpd", rem, bc, xl)

    # ---- inter-chunk recurrence over chunk states --------------------------
    if initial_state is None:
        s0 = jnp.zeros((bs, h, p, n), f32)
    else:
        s0 = initial_state.astype(f32)

    decay_chunk = jnp.exp(total)                            # (B,NC,H)

    def scan_fn(carry, inp):
        st_c, dk = inp                                      # (B,H,P,N),(B,H)
        new = carry * dk[:, :, None, None] + st_c
        return new, carry                                   # emit state *before* chunk

    final, prev_states = jax.lax.scan(
        scan_fn, s0,
        (states.swapaxes(0, 1), decay_chunk.swapaxes(0, 1)))
    prev_states = prev_states.swapaxes(0, 1)                # (B,NC,H,P,N)

    # ---- inter-chunk output: y_i += C_i · (decay_i · S_prev) ---------------
    dec_in = jnp.exp(cum)                                   # (B,NC,L,H)
    y_inter = jnp.einsum("bnid,bnih,bnhpd->bnihp", cc, dec_in, prev_states)

    y = (y_intra + y_inter).reshape(bs, s, h, p)
    return y.astype(x.dtype), final


def ssd_decode_step(state: jax.Array, x: jax.Array, dt: jax.Array,
                    a: jax.Array, b: jax.Array, c: jax.Array):
    """One-token SSD update.  state (B,H,P,N); x (B,H,P); dt (B,H); b,c (B,N)."""
    f32 = jnp.float32
    dk = jnp.exp(dt.astype(f32) * a.astype(f32))            # (B,H)
    dx = (x * dt[..., None]).astype(f32)                    # (B,H,P)
    new_state = state * dk[:, :, None, None] + \
        jnp.einsum("bhp,bd->bhpd", dx, b.astype(f32))
    y = jnp.einsum("bhpd,bd->bhp", new_state, c.astype(f32))
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------


def init_mamba2(key, d: int, cfg: SSMConfig, dtype=jnp.float32) -> dict:
    di = cfg.d_inner(d)
    nh = cfg.num_heads(d)
    n = cfg.d_state
    conv_dim = di + 2 * n
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt_bias = jnp.log(jnp.expm1(
        jnp.exp(jax.random.uniform(k4, (nh,),
                                   minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))))
    return {
        # order: [z (di), x (di), B (n), C (n), dt (nh)]
        "in_proj": layers.init_dense(k1, (d, 2 * di + 2 * n + nh), dtype),
        "conv_w": (jax.random.normal(k2, (cfg.d_conv, conv_dim)) * 0.1
                   ).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm": layers.init_rms_norm(di, dtype),
        "out_proj": layers.init_dense(k3, (di, d), dtype),
    }


def init_mamba2_cache(batch: int, d: int, cfg: SSMConfig,
                      dtype=jnp.float32) -> dict:
    di = cfg.d_inner(d)
    nh = cfg.num_heads(d)
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, di + 2 * cfg.d_state),
                          dtype),
        "ssm": jnp.zeros((batch, nh, cfg.head_dim, cfg.d_state), jnp.float32),
    }


def _causal_conv(xbc: jax.Array, w: jax.Array, bias: jax.Array,
                 cache: jax.Array | None):
    """Depthwise causal conv1d.  xbc (B,S,C); w (K,C).  Returns (y, new_cache)."""
    k = w.shape[0]
    if cache is None:
        pad = jnp.zeros_like(xbc[:, : k - 1])
    else:
        pad = cache.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)                # (B, S+K-1, C)
    new_cache = xp[:, -(k - 1):] if k > 1 else None
    y = sum(xp[:, i: i + xbc.shape[1]] * w[i][None, None, :]
            for i in range(k))
    return y + bias, new_cache


def mamba2_block(params: dict, x: jax.Array, cfg: SSMConfig, *,
                 cache: dict | None = None,
                 compute_dtype=jnp.bfloat16,
                 use_pallas: bool = False,
                 eps: float = 1e-6) -> tuple[jax.Array, dict | None]:
    """Apply one Mamba2 mixer.  x: (B, S, d) → (B, S, d); ``eps`` is the
    gated RMSNorm's."""
    bsz, s, d = x.shape
    di = cfg.d_inner(d)
    nh = cfg.num_heads(d)
    n = cfg.d_state

    zxbcdt = layers.dense(params["in_proj"], x, compute_dtype=compute_dtype)
    z, xbc, dt_raw = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)

    conv_cache = cache["conv"] if cache is not None else None
    xbc, new_conv = _causal_conv(xbc, params["conv_w"].astype(compute_dtype),
                                 params["conv_b"].astype(compute_dtype),
                                 conv_cache)
    xbc = jax.nn.silu(xbc)
    xin, b, c = jnp.split(xbc, [di, di + n], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"][None, None, :])  # (B,S,H)
    a = -jnp.exp(params["a_log"])                             # (H,) < 0
    xh = xin.reshape(bsz, s, nh, cfg.head_dim)

    if cache is None:
        with jax.named_scope(SSD_SCOPE):
            if use_pallas:
                from repro.kernels import ops as kops
                y, _ = kops.ssd_scan(xh, dt, a, b, c, chunk=cfg.chunk_size)
            else:
                y, _ = ssd_chunked(xh, dt, a, b, c,
                                   chunk=min(cfg.chunk_size, s))
        new_cache = None
    else:
        y1, new_ssm = ssd_decode_step(cache["ssm"], xh[:, 0], dt[:, 0], a,
                                      b[:, 0], c[:, 0])
        y = y1[:, None]
        new_cache = {"conv": new_conv, "ssm": new_ssm}

    y = y + params["d_skip"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(bsz, s, di)
    y = layers.rms_norm(params["norm"], y * jax.nn.silu(z), eps)
    out = layers.dense(params["out_proj"], y, compute_dtype=compute_dtype)
    return out, new_cache
