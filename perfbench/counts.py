"""Bytes computed from shapes, for kernels' roofline shares.

A model's operations per token are its reference's (``flops_per_token``
in ``references/<reference>.py``), beside the model they count.

``update_mix_bytes`` is the arithmetic of the fused SGD update+mix pass
(``src/repro/kernels/update_mix.py``; the byte model of
``launch/analysis.roundfuse_cost_model``): read x and g, write
y = W (x - eta g), each an (n, D) float32 buffer, plus the (n, n) W.
"""

from __future__ import annotations

__all__ = ["update_mix_bytes"]


def update_mix_bytes(n_agents: int, d: int, param_bytes: int = 4) -> float:
    """HBM bytes one fused SGD update+mix call must move."""
    return 3.0 * n_agents * d * param_bytes + n_agents * n_agents * 4
