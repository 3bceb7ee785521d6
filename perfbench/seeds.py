"""One PRNG key per purpose, derived from ``--seed``.

``--seed`` may exceed 32 bits; ``jax.random.key`` keeps only the low 32,
so the high part is folded in and distinct seeds give distinct keys.
"""

from __future__ import annotations

import jax

__all__ = ["root_key", "purpose_key"]

_PURPOSES = ("weights", "traffic", "rounds")


def root_key(seed: int) -> jax.Array:
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def purpose_key(seed: int, purpose: str) -> jax.Array:
    """Independent keys for the weights, the token pool and the rounds'
    W^t / server draws (the key the executor is called with)."""
    return jax.random.fold_in(root_key(seed), _PURPOSES.index(purpose))
