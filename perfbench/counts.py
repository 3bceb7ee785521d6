"""Operations and bytes computed from shapes, for MFU and roofline shares.

``model_flops_per_token`` counts what the forward and backward passes of a
configuration require per token: every matrix product (2 operations per
multiply-add), the attention score and value products over the causal
half that a token attends to, times 3 for forward plus backward.
Recomputation (the program's remat) is not counted.  Elementwise work,
norms and the softmax are left out.

``update_mix_bytes`` is the arithmetic of the fused SGD update+mix pass
(``src/repro/kernels/update_mix.py``; the byte model of
``launch/analysis.roundfuse_cost_model``): read x and g, write
y = W (x - eta g), each an (n, D) float32 buffer, plus the (n, n) W.
"""

from __future__ import annotations

__all__ = ["model_flops_per_token", "update_mix_bytes"]


def _dense_layer(arch: dict, seq: int) -> float:
    d, h, kv = arch["d_model"], arch["num_heads"], arch["num_kv_heads"]
    hd = arch.get("head_dim") or d // h
    proj = 2 * d * hd * (2 * h + 2 * kv)                # q, k, v, o
    mlp = 2 * d * arch["d_ff"] * (3 if arch.get("mlp_kind", "swiglu")
                                  in ("swiglu", "geglu") else 2)
    attn = 2 * 2 * h * hd * (seq + 1) / 2               # QK^T and PV, causal
    return proj + mlp + attn


def model_flops_per_token(arch: dict, seq: int) -> float:
    """Training operations per token (forward + backward = 3 x forward)."""
    if arch["arch_type"] != "dense":
        raise ValueError(f"no FLOP count for {arch['arch_type']!r} layers")
    head = 2 * arch["d_model"] * arch["vocab_size"]
    return 3.0 * (arch["num_layers"] * _dense_layer(arch, seq) + head)


def update_mix_bytes(n_agents: int, d: int, param_bytes: int = 4) -> float:
    """HBM bytes one fused SGD update+mix call must move."""
    return 3.0 * n_agents * d * param_bytes + n_agents * n_agents * 4
