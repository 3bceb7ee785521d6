"""Shared benchmark utilities: CSV emission, result paths, and the
linreg sweep-lattice setup shared by the figure scripts.

The figure scripts (fig2_alpha / fig4_convergence / theory_check) all drive
the same §4 linear-regression workload through the batched sweep engine
(repro.core.sweep): they build per-run key chains, stack per-cell mixing
setups, and reduce per-run final losses the same way.  Those pieces live
here so each script only describes its lattice.
"""

from __future__ import annotations

import argparse
import os

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                           "benchmarks")


def ensure_results_dir() -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return RESULTS_DIR


def write_csv(name: str, header: list[str], rows: list[tuple]) -> str:
    path = os.path.join(ensure_results_dir(), name)
    with open(path, "w") as f:
        f.write(",".join(header) + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")
    return path


def emit(name: str, us_per_call: float, derived: str) -> None:
    """The run.py contract: ``name,us_per_call,derived`` CSV lines."""
    print(f"{name},{us_per_call:.1f},{derived}")


def figure_arg_parser(description: str, *, t_steps: int | None = None,
                      seeds: int | None = None) -> argparse.ArgumentParser:
    """Shared --seeds/--t-steps/--smoke CLI for the figure scripts (they
    previously hardcoded module constants).  ``--smoke`` maps to each
    script's reduced CI settings (what run.py --quick passes)."""
    p = argparse.ArgumentParser(description=description)
    if t_steps is not None:
        p.add_argument("--t-steps", type=int, default=t_steps,
                       help=f"iterations T (default {t_steps})")
    if seeds is not None:
        p.add_argument("--seeds", type=int, default=seeds,
                       help=f"independent runs per cell (default {seeds})")
    p.add_argument("--smoke", action="store_true",
                   help="reduced T/seeds for CI smoke runs")
    return p


# ---------------------------------------------------------------------------
# Linreg sweep-lattice setup (shared by fig2/fig4/theory_check)
# ---------------------------------------------------------------------------


def paper_lr_fn(problem, h: int):
    """The Theorem-1 stepsize for a linreg cell: η_t = 2/(μ(γ(H)+t))."""
    from repro.core import theory
    return theory.paper_stepsize(
        problem.mu, theory.gamma(problem.l_smooth, problem.mu, h))


def paper_gamma(problem, h: int) -> float:
    from repro.core import theory
    return theory.gamma(problem.l_smooth, problem.mu, h)


def round_key_chains(seed_keys, n_rounds: int):
    """The figure drivers' per-round key split, precomputed per run.

    Reproduces ``key, kb, ks = jax.random.split(key, 3)`` chained from each
    run's seed key for ``n_rounds`` rounds.  Returns ``(kbs, kss)``, each a
    (R, n_rounds) key array: kb feeds minibatch sampling, ks is the round
    key handed to the executor.  Chains are prefixes of longer chains, so
    runs with fewer rounds (larger H) just use their leading columns.
    """
    import jax

    def chain(seed_key):
        def body(k, _):
            k, kb, ks = jax.random.split(k, 3)
            return k, (kb, ks)
        _, out = jax.lax.scan(body, seed_key, length=n_rounds)
        return out

    return jax.vmap(chain)(seed_keys)


def per_step_keys(kss, h_arr, t_steps: int):
    """(R, max_rounds) round keys → (T, R) per-step keys for the sweep
    round executor (``per_step_keys=True``): step s of run r runs inside
    round s // h_r and folds that round's key with the carried counter."""
    import jax.numpy as jnp
    r = kss.shape[0]
    rounds = jnp.arange(t_steps)[:, None] // jnp.asarray(h_arr)[None, :]
    return kss[jnp.arange(r)[None, :], rounds]


def lattice_minibatch_indices(kbs, h_arr, t_steps: int, n_agents: int,
                              m_batch: int, m_rows: int):
    """Per-step minibatch row indices (T, R, n, m) for the whole lattice.

    Reproduces each run's per-round draw
    ``jax.random.randint(kb, (h, n, m), 0, m_rows)`` — one (h, n, m) block
    per round key, concatenated along the step axis — grouped by H so every
    run's rows are bit-identical to the per-run driver's.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    h_arr = np.asarray(h_arr)
    r = h_arr.shape[0]
    idx_all = np.zeros((t_steps, r, n_agents, m_batch), dtype=np.int32)
    for h in np.unique(h_arr):
        runs = np.flatnonzero(h_arr == h)
        n_rounds = t_steps // int(h)
        draw = jax.jit(jax.vmap(jax.vmap(
            lambda k: jax.random.randint(
                k, (int(h), n_agents, m_batch), 0, m_rows))))
        blocks = draw(kbs[jnp.asarray(runs), :n_rounds])
        idx = np.asarray(blocks).reshape(len(runs), t_steps, n_agents,
                                         m_batch)
        idx_all[:, runs] = idx.transpose(1, 0, 2, 3)
    return idx_all


def sweep_minibatch_gather(problem):
    """(R, n, m) row indices → the per-agent (xb, yb) minibatch pytree the
    sweep step consumes; the batched form of the figure drivers'
    ``take_along_axis`` gather."""
    import jax.numpy as jnp
    xs = jnp.asarray(problem.x)
    ys = jnp.asarray(problem.y)

    def gather(idx):
        xb = jnp.take_along_axis(xs[None], idx[..., None], axis=2)
        yb = jnp.take_along_axis(ys[None], idx, axis=2)
        return xb, yb

    return gather


def sweep_suboptimality(problem):
    """(R, n, d) sweep buffer → per-run f(z̄) − f* (the Fig. 4 curve)."""
    import jax.numpy as jnp
    xs = jnp.asarray(problem.x)
    ys = jnp.asarray(problem.y)

    def subopt(flat3):
        zbar = flat3.mean(axis=1)                       # (R, d)
        res = jnp.einsum("imd,rd->rim", xs, zbar) - ys[None]
        return jnp.mean(jnp.sum(res * res, axis=-1),
                        axis=1) / problem.m_rows - problem.f_star

    return subopt
