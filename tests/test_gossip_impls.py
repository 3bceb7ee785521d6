"""Property-style equivalence of every gossip execution path.

All implementations of Algorithm 1 line 6 — dense einsum, leaf-wise and
whole-buffer Pallas kernels, CSR gather+segment_sum sparse, and the
mesh ppermute schedule — must compute the same mix for any W supported on
the graph (random doubly-stochastic Metropolis draws with link failures
included), over ragged leaf shapes and bf16 exchange.  The CSR metadata
itself (topology.csr_edges) is checked against the adjacency directly.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; the rest of the module runs
    from _hypothesis_stub import given, settings, st

from repro.core import flat as flat_lib
from repro.core import gossip, topology as topo
from repro.core.mixing import MixingDistribution
from repro.kernels import ops as kernel_ops

RAGGED_SHAPES = ((4,), (2, 3), (5, 1, 2), ())


def _stacked_tree(key, n, dtype=jnp.float32, shapes=RAGGED_SHAPES):
    ks = jax.random.split(key, len(shapes))
    return {f"w{i}": jax.random.normal(k, (n,) + s, dtype)
            for i, (k, s) in enumerate(zip(ks, shapes))}


def _sample_w(graph, seed, p_fail):
    md = MixingDistribution(graph, p_fail=p_fail, scheme="metropolis")
    return md.sample(jax.random.key(seed))


class TestCsrEdges:
    @pytest.mark.parametrize("graph", [
        topo.ring_graph(8, k=2), topo.geographic_graph(10, 0.6, seed=1),
        topo.chain_graph(5), topo.fully_connected_graph(6)])
    def test_matches_adjacency(self, graph):
        recv, send, indptr = topo.csr_edges(graph)
        assert len(recv) == len(send) == int(graph.adjacency.sum())
        assert indptr[0] == 0 and indptr[-1] == len(recv)
        np.testing.assert_array_equal(np.diff(indptr), graph.degrees)
        assert (np.diff(recv) >= 0).all()  # receiver-sorted
        for r, s in zip(recv, send):
            assert graph.adjacency[r, s]
        assert not np.any(recv == send)  # no self-loops

    def test_isolated_graph_empty(self):
        g = topo.Graph(np.zeros((4, 4), dtype=bool))
        recv, send, indptr = topo.csr_edges(g)
        assert len(recv) == 0
        np.testing.assert_array_equal(indptr, np.zeros(5, np.int32))


class TestImplEquivalence:
    """dense == pallas == sparse (tree and flat layouts) on random W."""

    @given(st.integers(0, 30), st.sampled_from([0.0, 0.3, 0.6]))
    @settings(max_examples=10, deadline=None)
    def test_tree_impls_match_dense(self, seed, p_fail):
        n = 9
        graph = topo.geographic_graph(n, 0.6, seed=2)
        w = _sample_w(graph, seed, p_fail)
        x = _stacked_tree(jax.random.key(seed + 1), n)
        ref = gossip.gossip_mix_dense(w, x)
        via_pallas = kernel_ops.gossip_mix_tree(w, x)
        via_sparse = gossip.make_sparse_gossip_tree(graph)(w, x)
        for k in x:
            np.testing.assert_allclose(np.asarray(via_pallas[k]),
                                       np.asarray(ref[k]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(via_sparse[k]),
                                       np.asarray(ref[k]), atol=1e-5)

    @given(st.integers(0, 30))
    @settings(max_examples=10, deadline=None)
    def test_flat_impls_match_dense(self, seed):
        n, d = 8, 300
        graph = topo.ring_graph(n, k=2)
        w = _sample_w(graph, seed, p_fail=0.4)
        x = jax.random.normal(jax.random.key(seed), (n, d))
        ref = jnp.einsum("ij,jd->id", w, x,
                         precision=jax.lax.Precision.HIGHEST)
        np.testing.assert_allclose(np.asarray(kernel_ops.gossip_mix(w, x)),
                                   np.asarray(ref), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(gossip.make_sparse_gossip(graph)(w, x)),
            np.asarray(ref), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(kernel_ops.make_sparse_gossip_pallas(graph)(w, x)),
            np.asarray(ref), atol=1e-5)

    def test_bf16_exchange(self):
        """bf16 leaves: every impl stays within bf16 resolution of dense."""
        n = 8
        graph = topo.ring_graph(n, k=2)
        w = _sample_w(graph, 3, p_fail=0.0)
        x = _stacked_tree(jax.random.key(7), n, dtype=jnp.bfloat16,
                          shapes=((64,), (4, 5)))
        ref = gossip.gossip_mix_dense(w, x)
        via_pallas = kernel_ops.gossip_mix_tree(w, x)
        via_sparse = gossip.make_sparse_gossip_tree(graph)(w, x)
        for k in x:
            assert via_pallas[k].dtype == jnp.bfloat16
            assert via_sparse[k].dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(via_pallas[k], np.float32),
                np.asarray(ref[k], np.float32), atol=2e-2, rtol=2e-2)
            np.testing.assert_allclose(
                np.asarray(via_sparse[k], np.float32),
                np.asarray(ref[k], np.float32), atol=2e-2, rtol=2e-2)

    def test_sparse_respects_link_failures(self):
        """Edges zeroed by the sampled W contribute nothing (same as dense)."""
        n = 10
        graph = topo.geographic_graph(n, 0.7, seed=4)
        w = _sample_w(graph, 11, p_fail=0.7)
        x = jax.random.normal(jax.random.key(0), (n, 17))
        ref = jnp.einsum("ij,jd->id", w, x,
                         precision=jax.lax.Precision.HIGHEST)
        got = gossip.make_sparse_gossip(graph)(w, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    def test_sparse_mean_preservation(self):
        """Doubly stochastic W keeps x̄ (Lemma 2 invariant) on the CSR path."""
        n = 12
        graph = topo.ring_graph(n, k=3)
        w = _sample_w(graph, 5, p_fail=0.2)
        x = jax.random.normal(jax.random.key(1), (n, 33))
        y = gossip.make_sparse_gossip(graph)(w, x)
        np.testing.assert_allclose(np.asarray(y.mean(0)),
                                   np.asarray(x.mean(0)), atol=1e-5)

    def test_flat_spec_roundtrip_ragged(self):
        n = 6
        x = _stacked_tree(jax.random.key(2), n)
        spec = flat_lib.make_flat_spec_from_stacked(x)
        buf = spec.flatten(x)
        assert buf.shape == (n, spec.d)
        back = spec.unflatten(buf)
        for k in x:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(x[k]))


_PERMUTE_EQUIV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from repro.core import gossip, topology as topo
from repro.core.mixing import MixingDistribution
from repro.kernels import ops as kernel_ops

n = 8
mesh = jax.make_mesh((n,), ("agents",))
g = topo.geographic_graph(n, 0.7, seed=5)
md = MixingDistribution(g, p_fail=0.3, scheme="metropolis")
w = md.sample(jax.random.key(7))
x = {"a": jax.random.normal(jax.random.key(1), (n, 16)),
     "b": jax.random.normal(jax.random.key(2), (n, 4, 4))}
dense = gossip.gossip_mix_dense(w, x)
sparse = gossip.make_sparse_gossip_tree(g)(w, x)
pallas = kernel_ops.gossip_mix_tree(w, x)
perm_fn = gossip.make_permute_gossip(g, mesh, "agents")
perm_bf16 = gossip.make_permute_gossip(g, mesh, "agents",
                                       exchange_dtype=jnp.bfloat16)
with jax.set_mesh(mesh):
    permuted = jax.jit(perm_fn)(w, x)
    permuted_bf16 = jax.jit(perm_bf16)(w, x)
for k in x:
    for name, other, tol in [("permute", permuted, 1e-5),
                             ("sparse", sparse, 1e-5),
                             ("pallas", pallas, 1e-5),
                             ("permute_bf16_exchange", permuted_bf16, 2e-2)]:
        np.testing.assert_allclose(np.asarray(dense[k]),
                                   np.asarray(other[k]), atol=tol,
                                   err_msg=name)
print("ALL_IMPLS_OK")
"""


def test_all_impls_match_dense_subprocess():
    """dense == pallas == sparse == permute on one shared random W.

    The ppermute path needs an 8-device mesh; runs in a subprocess so the
    host-platform override never leaks into this session (1 CPU device).
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", _PERMUTE_EQUIV],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr
    assert "ALL_IMPLS_OK" in res.stdout
