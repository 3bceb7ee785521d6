"""Plain reference of a dense decoder LM with RoPE, GQA and a gated MLP.

The published Qwen1.5 block (pre-norm RMSNorm, q/k/v projections with
bias, rotary embeddings on halves of each head, causal softmax attention,
SwiGLU MLP, final norm, untied head) written straight in ``jax.numpy``,
one layer after another, with no kernels, no scan and no fusion.  Every
product goes through ``mm``, which the caller sets: float32 at HIGHEST
precision for the reference, or a lower precision for the control.

It reads the parameter tree the benchmark made (``perfbench/weights.py``)
by its keys.  Where the program departs from the published model, the
reference follows the program, and the configuration file lists each
departure (the ``sqrt(d_model)`` embedding scale; RMSNorm as ``1 + scale``).

``flops_per_token`` counts what the forward and backward passes of this
model require per token: every matrix product (2 operations per
multiply-add), the attention score and value products over the causal
half that a token attends to, times 3 for forward plus backward.
Recomputation (the program's remat) is not counted.  Elementwise work,
norms and the softmax are left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.references import layer_params

__all__ = ["loss", "flops_per_token"]


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """x (B, S, H, hd): rotate the two halves of each head by position."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, arch, mm):
    heads, kv = arch["num_heads"], arch["num_kv_heads"]
    q = mm("bsd,dhk->bshk", h, p["wq"]["w"])
    k = mm("bsd,dhk->bshk", h, p["wk"]["w"])
    v = mm("bsd,dhk->bshk", h, p["wv"]["w"])
    if "b" in p["wq"]:
        q, k, v = q + p["wq"]["b"], k + p["wk"]["b"], v + p["wv"]["b"]
    q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    s = q.shape[1]
    scores = mm("bqhk,bthk->bhqt", q, k) / jnp.sqrt(float(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = mm("bhqt,bthk->bqhk", probs, v)
    return mm("bqhk,hkd->bqd", out, p["wo"]["w"])


def _mlp(p, h, mm):
    gate = jax.nn.silu(mm("bsd,df->bsf", h, p["wg"]["w"]))
    up = mm("bsd,df->bsf", h, p["wi"]["w"])
    return mm("bsf,fd->bsd", gate * up, p["wo"]["w"])


def loss(params, tokens, arch, mm):
    """Mean next-token cross entropy of ``tokens`` (B, S)."""
    eps = arch["norm_eps"]
    table = params["embed"]["table"]
    x = table[tokens] * jnp.sqrt(float(arch["d_model"]))
    for lp in layer_params(params["stack"]):
        x = x + _attention(lp["attn"], _rms(x, lp["norm1"]["scale"], eps),
                           arch, mm)
        x = x + _mlp(lp["mlp"], _rms(x, lp["norm2"]["scale"], eps), mm)
    x = _rms(x, params["final_norm"]["scale"], eps)
    if arch.get("tie_embeddings"):
        logits = mm("bsd,vd->bsv", x, table)
    else:
        logits = mm("bsd,dv->bsv", x, params["head"]["w"])
    logits = logits[:, :-1]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def _dense_layer(arch: dict, seq: int) -> float:
    d, h, kv = arch["d_model"], arch["num_heads"], arch["num_kv_heads"]
    hd = arch.get("head_dim") or d // h
    proj = 2 * d * hd * (2 * h + 2 * kv)                # q, k, v, o
    mlp = 2 * d * arch["d_ff"] * (3 if arch.get("mlp_kind", "swiglu")
                                  in ("swiglu", "geglu") else 2)
    attn = 2 * 2 * h * hd * (seq + 1) / 2               # QK^T and PV, causal
    return proj + mlp + attn


def flops_per_token(arch: dict, seq: int) -> float:
    """Training operations per token (forward + backward = 3 x forward)."""
    if arch["arch_type"] != "dense":
        raise ValueError(f"no FLOP count for {arch['arch_type']!r} layers")
    head = 2 * arch["d_model"] * arch["vocab_size"]
    return 3.0 * (arch["num_layers"] * _dense_layer(arch, seq) + head)
