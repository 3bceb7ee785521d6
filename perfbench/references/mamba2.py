"""Plain reference of the Mamba-2 language model (arXiv:2405.21060).

The published model (``mamba_ssm``'s ``MambaLMHeadModel`` with ``Mamba2``
mixers) written straight in ``jax.numpy``, one layer after another, with
no kernels, no scan and no chunking:

* the tied embedding, not scaled;
* per layer ``x + mixer(rmsnorm(x))``; the mixer projects ``x`` to
  ``z``, ``xBC`` and ``dt``; a causal depthwise convolution of width
  ``d_conv`` with bias, then SiLU, gives ``x``, ``B`` and ``C`` (one group,
  shared by the heads); ``dt = softplus(dt + dt_bias)``, ``A = -exp(a_log)``;
* the SSM in its dual quadratic form over the whole sequence,
  ``y = (L o C B^T) (dt x)`` with ``L[i, j] = exp(sum_{j<k<=i} dt_k A)``
  for ``i >= j`` and 0 above, computed for a few heads at a time so that
  the (heads, seq, seq) matrices fit; then ``y + D x``, the gated norm
  ``rmsnorm(y * silu(z))`` and the output projection;
* the final norm and the tied head.

Every product goes through ``mm``, which the caller sets: float32 at
HIGHEST precision for the reference, or a lower precision for the control.
It reads the parameter tree the benchmark made (``perfbench/weights.py``)
by its keys.  Where the program departs from the published model, the
reference follows the program, and the configuration file lists each
departure.

``flops_per_token`` counts what the forward and backward passes of the
program's chunked algorithm require per token, recomputation not counted
(``_ssm_layer``'s docstring lists each term).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.references import layer_params

__all__ = ["loss", "flops_per_token"]

# heads whose (seq, seq) decay matrices are built at once
HEAD_BLOCK = 8


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _segsum(la):
    """la (B, H, S) -> (B, H, S, S): sum of la[k] over j < k <= i at
    [i, j] for i >= j, -inf above: each entry is its own sum, so it does
    not lose the digits that a difference of two long prefix sums would."""
    s = la.shape[-1]
    below = jnp.tril(jnp.ones((s, s), bool), -1)
    sums = jnp.cumsum(jnp.where(below, la[..., :, None], 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((s, s), bool)), sums, -jnp.inf)


@partial(jax.checkpoint, static_argnums=3)
def _ssd_heads(cb, la, dx, mm):
    """y (B, S, h, P) of a block of h heads: (L o CB) (dt x).  Kept
    for the backward is only what goes in: the block's (h, S, S) matrices
    are made again there."""
    m = jnp.exp(_segsum(la)) * cb[:, None]
    return mm("bhij,bjhp->bihp", m, dx)


def _conv(xbc, w, bias, mm):
    """Causal depthwise convolution: xbc (B, S, C), w (K, C)."""
    k, s = w.shape[0], xbc.shape[1]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    taps = jnp.stack([xp[:, i:i + s] for i in range(k)], axis=2)
    return mm("bskc,kc->bsc", taps, w) + bias


def _mixer(p, h, arch, mm):
    ssm = arch["ssm"]
    di = ssm["expand"] * arch["d_model"]
    n, hd = ssm["d_state"], ssm["head_dim"]
    heads = di // hd
    zxbcdt = mm("bsd,de->bse", h, p["in_proj"]["w"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    xbc = jax.nn.silu(_conv(xbc, p["conv_w"], p["conv_b"], mm))
    x, b, c = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # (B, S, H)
    a = -jnp.exp(p["a_log"])                                 # (H,)
    x = x.reshape(x.shape[:2] + (heads, hd))                 # (B, S, H, P)
    cb = mm("bin,bjn->bij", c, b)                            # (B, S, S)
    la = jnp.swapaxes(dt * a, 1, 2)                          # (B, H, S)
    dx = x * dt[..., None]
    y = jnp.concatenate([
        _ssd_heads(cb, la[:, h0:h0 + HEAD_BLOCK],
                   dx[:, :, h0:h0 + HEAD_BLOCK], mm)
        for h0 in range(0, heads, HEAD_BLOCK)], axis=2)
    y = y + p["d_skip"][:, None] * x
    y = y.reshape(y.shape[:2] + (di,))
    y = _rms(y * jax.nn.silu(z), p["norm"]["scale"], arch["norm_eps"])
    return mm("bse,ed->bsd", y, p["out_proj"]["w"])


def loss(params, tokens, arch, mm):
    """Mean next-token cross entropy of ``tokens`` (B, S)."""
    eps = arch["norm_eps"]
    table = params["embed"]["table"]
    x = table[tokens]
    for lp in layer_params(params["stack"]):
        x = x + _mixer(lp["mixer"], _rms(x, lp["norm1"]["scale"], eps),
                       arch, mm)
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = mm("bsd,vd->bsv", x, table)[:, :-1]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def _ssm_layer(arch: dict, seq: int) -> float:
    """Forward operations of one Mamba-2 layer per token (2 per
    multiply-add), as the program's chunked scan computes it with chunks
    of L = min(chunk_size, seq), H heads of P, state N, one group:

    * in_proj  2 d (2 H P + 2 N + H);   out_proj  2 H P d;
    * the convolution  2 d_conv (H P + 2 N);
    * intra-chunk  C B^T  2 N (L + 1) / 2 and  (L o CB) (dt x)
      2 H P (L + 1) / 2: a token meets the (L + 1) / 2 positions of its
      chunk at or before it on average, as the causal half of attention;
    * the chunk states  sum_j B_j (dt x)_j  2 H P N;
    * the inter-chunk output  C_i . (decay S_prev)  2 H P N.

    The recurrence over chunk states (H P N a chunk), norms, gates and
    other elementwise work are left out."""
    d, ssm = arch["d_model"], arch["ssm"]
    n, p, k = ssm["d_state"], ssm["head_dim"], ssm["d_conv"]
    hp = ssm["expand"] * d
    h = hp // p
    chunk = min(ssm["chunk_size"], seq)
    proj = 2 * d * (2 * hp + 2 * n + h) + 2 * hp * d
    conv = 2 * k * (hp + 2 * n)
    intra = 2 * n * (chunk + 1) / 2 + 2 * hp * (chunk + 1) / 2
    states = 2 * 2 * hp * n
    return proj + conv + intra + states


def flops_per_token(arch: dict, seq: int) -> float:
    """Training operations per token: 3 x the forward (forward + backward)
    of every layer and the tied head (2 d V); the embedding lookup is a
    gather, and its gradient d additions."""
    if arch["arch_type"] != "ssm":
        raise ValueError(f"no FLOP count for {arch['arch_type']!r} layers")
    head = 2 * arch["d_model"] * arch["vocab_size"]
    return 3.0 * (arch["num_layers"] * _ssm_layer(arch, seq) + head) \
        + arch["d_model"]
