"""Shared neural-net building blocks (pure-functional JAX, no flax).

Conventions:
  * params are nested dicts of jnp arrays; init fns take (key, cfg-ish args)
    and return the dict; apply fns take (params, inputs).
  * weights are stored in ``param_dtype`` and cast to ``compute_dtype`` at
    use; layernorm math in float32.
  * matmul dims are laid out so the tensor-parallel axis is the contraction
    output: wq (d, H, hd), wo (H, hd, d), wi (d, ff), wd (ff, d).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "rms_norm", "init_rms_norm", "init_layer_norm", "layer_norm",
    "init_dense", "dense",
    "init_mlp", "mlp",
    "init_embedding", "embed", "unembed",
    "rope_frequencies", "apply_rope", "apply_mrope",
]


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------


def init_rms_norm(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.zeros((d,), dtype=dtype)}  # (1 + scale) convention


def rms_norm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dtype)


def init_layer_norm(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype=dtype),
            "bias": jnp.zeros((d,), dtype=dtype)}


def layer_norm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# Dense / MLP
# ---------------------------------------------------------------------------


def init_dense(key, shape: tuple[int, ...], dtype=jnp.float32,
               bias: bool = False, fan_in: int | None = None) -> dict:
    """Truncated-normal init scaled by 1/sqrt(fan_in) (first dim by default)."""
    fan = fan_in if fan_in is not None else shape[0]
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape) / jnp.sqrt(fan)
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros(shape[1:], dtype=dtype)
    return p


def dense(params: dict, x: jax.Array, contract: int = 1,
          compute_dtype=None) -> jax.Array:
    """x @ w contracting x's last `contract` dims with w's first `contract`."""
    w = params["w"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
        x = x.astype(compute_dtype)
    y = jax.lax.dot_general(
        x, w, (((tuple(range(x.ndim - contract, x.ndim))),
                tuple(range(contract))), ((), ())))
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


_ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def init_mlp(key, d: int, d_ff: int, kind: str = "swiglu",
             dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    gated = kind in ("swiglu", "geglu")
    p = {"wi": init_dense(k1, (d, d_ff), dtype),
         "wo": init_dense(k3, (d_ff, d), dtype)}
    if gated:
        p["wg"] = init_dense(k2, (d, d_ff), dtype)
    return p


def mlp(params: dict, x: jax.Array, kind: str = "swiglu",
        compute_dtype=None) -> jax.Array:
    """SwiGLU / GeGLU / squared-ReLU / GELU feed-forward."""
    h = dense(params["wi"], x, compute_dtype=compute_dtype)
    if kind == "swiglu":
        h = jax.nn.silu(dense(params["wg"], x, compute_dtype=compute_dtype)) * h
    elif kind == "geglu":
        h = jax.nn.gelu(dense(params["wg"], x, compute_dtype=compute_dtype)) * h
    elif kind == "relu2":
        h = _ACTS["relu2"](h)
    elif kind == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(f"unknown mlp kind {kind!r}")
    return dense(params["wo"], h, compute_dtype=compute_dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d: int, dtype=jnp.float32) -> dict:
    tbl = jax.random.normal(key, (vocab, d)) * 0.02
    return {"table": tbl.astype(dtype)}


def embed(params: dict, tokens: jax.Array, compute_dtype=None) -> jax.Array:
    tbl = params["table"]
    if compute_dtype is not None:
        tbl = tbl.astype(compute_dtype)
    return jnp.take(tbl, tokens, axis=0)


def unembed(params: dict, x: jax.Array, compute_dtype=None) -> jax.Array:
    """Logits via the (untied) output head; params = {'w': (d, vocab)}."""
    return dense(params, x, compute_dtype=compute_dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding (+ multimodal M-RoPE for Qwen2-VL)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float = 10_000.0) -> jax.Array:
    """Inverse frequencies for the even half of the head dim."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)  # (head_dim // 2,)


def _rotate(x: jax.Array, angles: jax.Array) -> jax.Array:
    # x: (..., S, n_heads, head_dim); angles: (..., S, head_dim//2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10_000.0) -> jax.Array:
    """Standard RoPE.  x: (..., S, H, hd); positions: (..., S) int.

    Lowered as the degenerate M-RoPE (all three bands carry the sequence
    position — numerics identical, multiply for multiply): the band-gather
    keeps the angle tensor replicated over a partially-auto mesh axis,
    where the plain ``positions[..., None] * inv`` broadcast lets GSPMD
    tile the head dim and the rotate's concatenate then fails XLA's
    manual-subgroup check inside the 2-D sharded engine's region.
    """
    p3 = jnp.broadcast_to(positions[None], (3,) + positions.shape)
    return apply_mrope(x, p3, theta)


def apply_mrope(x: jax.Array, positions_3d: jax.Array,
                theta: float = 10_000.0,
                sections: tuple[int, int, int] | None = None) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL, arXiv:2409.12191 §2.1).

    The head dim's frequency bands are split into (temporal, height, width)
    sections; each section rotates by its own position component.

    Args:
      x: (..., S, H, hd).
      positions_3d: (3, ..., S) int — (t, h, w) ids; for pure text all three
        equal the sequence position (M-RoPE then reduces to RoPE exactly).
    """
    hd = x.shape[-1]
    half = hd // 2
    if sections is None:
        t_sec = half - 2 * (half // 4)
        sections = (t_sec, half // 4, half // 4)
    assert sum(sections) == half, (sections, half)
    inv = rope_frequencies(hd, theta)  # (half,)
    sec_id = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                        total_repeat_length=half)  # (half,)
    pos = positions_3d.astype(jnp.float32)  # (3, ..., S)
    # pick the position component per frequency band
    pos_per_band = jnp.take(pos, sec_id, axis=0)       # (half, ..., S)
    pos_per_band = jnp.moveaxis(pos_per_band, 0, -1)   # (..., S, half)
    angles = pos_per_band * inv
    return _rotate(x, angles)
