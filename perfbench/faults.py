"""Faults planted under the timed path, to show that ``correct`` catches
them.  The tests plant them in the program (``wrappers``); ``control.py``
plants the same faults, by name, in the reference put in the program's
place (``harness.reference``).  Never used by a benchmark run.

* ``frozen``      every step returns its state unchanged (step size 0;
  the agents start equal, so mixing and the server keep them so);
* ``half_batch``  the gradient sees half of each agent's batch (the first
  half of every sequence) and takes its mean over that half;
* ``no_mix``      the gossip mix is left out: W = I;
* ``one_edge``    one weight of W is wrong: agent 0 drops its link to
  agent 1 and keeps that weight on its own row (W is then no longer
  symmetric, as a kernel that misreads one entry would make it).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["FAULTS", "wrappers", "mixing_matrix"]

FAULTS = ("frozen", "half_batch", "no_mix", "one_edge")


def mixing_matrix(fault: str | None, w: np.ndarray) -> np.ndarray:
    """W as the fault leaves it (``w`` itself for the others)."""
    if fault == "no_mix":
        return np.eye(w.shape[0], dtype=w.dtype)
    if fault == "one_edge":
        w = w.copy()
        w[0, 0] += w[0, 1]
        w[0, 1] = 0.0
    return w


def _half_batch(grad_fn):
    def fn(params, batch, key):
        half = batch["tokens"].shape[-1] // 2
        return grad_fn(params, {k: v[..., :half] for k, v in batch.items()},
                       key)
    return fn


def _wrong_mixing(fault: str):
    """Wraps a FedDecConfig so that every W^t it draws is the fault's."""
    from repro.core.mixing import MixingDistribution

    @jax.tree_util.register_static
    @dataclasses.dataclass(frozen=True)
    class WrongMixing(MixingDistribution):
        def sample(self, key):
            w = mixing_matrix(fault, np.asarray(self.fixed_w, np.float64))
            return jnp.asarray(w, self.dtype)

    def wrap(fcfg):
        m = fcfg.mixing
        return dataclasses.replace(fcfg, mixing=WrongMixing(
            m.graph, m.p_fail, m.scheme, m.dtype))
    return wrap


def wrappers(fault: str | None) -> tuple:
    """(wrap_grad, wrap_lr, wrap_fed) that plant ``fault`` in the program
    the harness builds."""
    same = lambda f: f  # noqa: E731
    if fault is None:
        return same, same, same
    if fault == "frozen":
        return same, lambda lr_fn: (lambda t: jnp.zeros((), jnp.float32)), same
    if fault == "half_batch":
        return _half_batch, same, same
    if fault in ("no_mix", "one_edge"):
        return same, same, _wrong_mixing(fault)
    raise ValueError(f"unknown fault {fault!r}; choose from {FAULTS}")
