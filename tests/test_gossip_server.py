"""Tests for gossip averaging and server aggregation.

Key invariants from the paper's analysis:
  * doubly stochastic W preserves the agent-mean exactly
    (x̄^{t+1} = x̄^{t+1/2}, used inside Lemma 2's first equality);
  * repeated gossip contracts the consensus error at rate |λ̂₂| (Lemma 3);
  * the server round satisfies E_{S_t}[z̄] = x̄ (eq. (7));
  * the ppermute schedule equals the dense einsum bit-for-bit (same W).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; the rest of the module runs
    from _hypothesis_stub import given, settings, st

from repro.core import gossip, server, topology as topo
from repro.core.mixing import MixingDistribution


def _stacked_tree(key, n, shapes=((4,), (2, 3))):
    ks = jax.random.split(key, len(shapes))
    return {f"w{i}": jax.random.normal(k, (n,) + s)
            for i, (k, s) in enumerate(zip(ks, shapes))}


class TestDenseGossip:
    @given(st.integers(0, 20), st.floats(0.0, 0.8))
    @settings(max_examples=15, deadline=None)
    def test_mean_preservation(self, seed, p_fail):
        n = 10
        g = topo.geographic_graph(n, 0.6, seed=1)
        md = MixingDistribution(g, p_fail=p_fail, scheme="metropolis")
        w = md.sample(jax.random.key(seed))
        x = _stacked_tree(jax.random.key(seed + 1), n)
        y = gossip.gossip_mix_dense(w, x)
        for k in x:
            np.testing.assert_allclose(
                np.asarray(y[k].mean(0)), np.asarray(x[k].mean(0)),
                atol=1e-5)

    def test_consensus_contraction(self):
        """‖X − X̄‖² shrinks by ≈ |λ₂|² per fixed-W gossip round (Lemma 3)."""
        n = 16
        g = topo.geographic_graph(n, 0.6, seed=2)
        w = jnp.asarray(topo.laplacian_weights(g), dtype=jnp.float64) \
            if jax.config.jax_enable_x64 else \
            jnp.asarray(topo.laplacian_weights(g), dtype=jnp.float32)
        lam2 = topo.lambda2(np.asarray(w))
        x = jax.random.normal(jax.random.key(0), (n, 32))

        def cons_err(z):
            return float(((z - z.mean(0)) ** 2).sum())

        e0 = cons_err(x)
        y = gossip.gossip_mix_dense(w, x)
        e1 = cons_err(y)
        assert e1 <= lam2 ** 2 * e0 + 1e-4  # Fact 4 bound

    def test_identity_w_noop(self):
        x = _stacked_tree(jax.random.key(0), 6)
        y = gossip.gossip_mix_dense(jnp.eye(6), x)
        for k in x:
            np.testing.assert_allclose(np.asarray(y[k]), np.asarray(x[k]),
                                       atol=1e-6)


class TestServer:
    def test_counts_sum_to_k(self):
        c = server.sample_participants(jax.random.key(0), 20, 7)
        assert int(c.sum()) == 7

    def test_broadcast_equalises(self):
        x = _stacked_tree(jax.random.key(1), 8)
        out = server.server_round(jax.random.key(2), x, k=3)
        for k in out:
            first = out[k][0]
            for i in range(8):
                np.testing.assert_allclose(np.asarray(out[k][i]),
                                           np.asarray(first), atol=1e-6)

    def test_unbiasedness_eq7(self):
        """E_{S_t}[z̄] = x̄ over many samplings (paper eq. (7))."""
        n, k = 10, 3
        x = jax.random.normal(jax.random.key(3), (n, 5))
        keys = jax.random.split(jax.random.key(4), 4000)

        def zbar(key):
            c = server.sample_participants(key, n, k)
            wts = server.participant_weights(c, k)
            return jnp.tensordot(wts, x, axes=(0, 0))

        zb = jax.vmap(zbar)(keys).mean(0)
        np.testing.assert_allclose(np.asarray(zb), np.asarray(x.mean(0)),
                                   atol=0.05)

    def test_full_participation_exact_mean(self):
        # K = n with a deterministic count of one each ⇒ plain mean
        x = _stacked_tree(jax.random.key(5), 4)
        wts = jnp.full((4,), 0.25)
        out = server.aggregate_and_broadcast(wts, x)
        for k in x:
            np.testing.assert_allclose(np.asarray(out[k][0]),
                                       np.asarray(x[k].mean(0)), atol=1e-6)


_PERMUTE_EQUIV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import gossip, topology as topo
from repro.core.mixing import MixingDistribution

n = 8
mesh = jax.make_mesh((n,), ("agents",))
g = topo.geographic_graph(n, 0.7, seed=5)
md = MixingDistribution(g, p_fail=0.3, scheme="metropolis")
w = md.sample(jax.random.key(7))
x = {"a": jax.random.normal(jax.random.key(1), (n, 16)),
     "b": jax.random.normal(jax.random.key(2), (n, 4, 4))}
dense = gossip.gossip_mix_dense(w, x)
perm_fn = gossip.make_permute_gossip(g, mesh, "agents")
with jax.set_mesh(mesh):
    permuted = jax.jit(perm_fn)(w, x)
for k in x:
    np.testing.assert_allclose(np.asarray(dense[k]), np.asarray(permuted[k]),
                               atol=1e-5)
print("PERMUTE_OK")
"""


def test_permute_gossip_matches_dense_subprocess():
    """The neighbour-only ppermute schedule equals the dense path.

    Runs in a subprocess so the 8-device host-platform override never leaks
    into this test session (which must keep seeing 1 CPU device).
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", _PERMUTE_EQUIV],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr
    assert "PERMUTE_OK" in res.stdout
