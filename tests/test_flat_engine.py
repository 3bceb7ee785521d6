"""Flat-engine contract tests: FlatSpec ravel, state conversion, and the
flat executor's own behavioural guarantees (server consensus inside the
scan, donation, metrics_fn).

The tree ≡ flat trajectory-equivalence grid that used to live here moved
to tests/conformance/test_grid.py — one differential harness covering all
four engine lowerings against the single flat reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import FedDecConfig, init_state
from repro.core import flat as flat_lib
from repro.core import server, theory, topology as topo
from repro.core.mixing import MixingDistribution
from repro.data import linreg

N_AGENTS = 8
H_CFG = 4        # server period — windows below deliberately cross it


@pytest.fixture(scope="module")
def problem():
    return linreg.make_problem(n=N_AGENTS, seed=0, c_base=1.3)


@pytest.fixture(scope="module")
def spec(problem):
    return flat_lib.make_flat_spec(jnp.zeros(problem.d))


def _setup(problem, *, p_fail=0.0, gossip_impl="dense", server_enabled=True):
    g = topo.geographic_graph(problem.n, 0.6, seed=3)
    md = MixingDistribution(g, p_fail=p_fail,
                            scheme="metropolis" if p_fail else "laplacian")
    cfg = FedDecConfig(mixing=md, h=H_CFG, k=2,
                       server_enabled=server_enabled,
                       gossip_impl=gossip_impl)
    lr = theory.paper_stepsize(
        problem.mu, theory.gamma(problem.l_smooth, problem.mu, H_CFG))
    grad_fn = linreg.make_grad_fn(problem.m_rows)
    return cfg, lr, grad_fn


def _stacked_batches(problem, t_steps, seed=11):
    keys = jax.random.split(jax.random.key(seed), t_steps)
    return jax.vmap(lambda k: linreg.sample_minibatch(problem, k, m=1))(keys)


class TestFlatContract:
    def test_server_consensus_inside_scan(self, problem, spec):
        """A window ending exactly on t+1 = H equalises every buffer row."""
        cfg, lr, grad_fn = _setup(problem)  # h=4, server at t+1=4
        flat_round = flat_lib.make_flat_feddec_round(cfg, spec, grad_fn, lr,
                                                     donate=False)
        batches = _stacked_batches(problem, 3)  # t: 1,2,3 → server at t+1=4
        state, _ = flat_round(
            flat_lib.init_flat_state(spec, jnp.zeros(problem.d), problem.n),
            batches, jax.random.key(2))
        p = np.asarray(state.flat)
        np.testing.assert_allclose(p, np.broadcast_to(p[:1], p.shape),
                                   atol=1e-5)

    def test_donation_round_over_round(self, problem, spec):
        cfg, lr, grad_fn = _setup(problem)
        flat_round = flat_lib.make_flat_feddec_round(cfg, spec, grad_fn, lr,
                                                     donate=True)
        state = flat_lib.init_flat_state(spec, jnp.zeros(problem.d),
                                         problem.n)
        for r in range(3):
            batches = _stacked_batches(problem, 4, seed=20 + r)
            state, _ = flat_round(state, batches, jax.random.key(3))
        assert int(state.step) == 13
        assert np.isfinite(np.asarray(state.flat)).all()

    def test_metrics_fn_on_flat_state(self, problem, spec):
        cfg, lr, grad_fn = _setup(problem)
        flat_round = flat_lib.make_flat_feddec_round(
            cfg, spec, grad_fn, lr, donate=False,
            metrics_fn=lambda s: {
                "subopt": problem.suboptimality(spec.unflatten(s.flat))})
        batches = _stacked_batches(problem, 5)
        _, m = flat_round(
            flat_lib.init_flat_state(spec, jnp.zeros(problem.d), problem.n),
            batches, jax.random.key(0))
        assert m["subopt"].shape == (5,)
        assert np.isfinite(np.asarray(m["subopt"])).all()

    def test_flat_server_round_matches_tree(self):
        """server_round_flat == server_round on the flattened pytree."""
        n, k = 8, 3
        key = jax.random.key(4)
        tree = {"a": jax.random.normal(key, (n, 5, 2)),
                "b": jax.random.normal(jax.random.fold_in(key, 1), (n, 7))}
        spec = flat_lib.make_flat_spec_from_stacked(tree)
        buf = spec.flatten(tree)
        skey = jax.random.key(6)
        out_tree = server.server_round(skey, tree, k)
        out_flat = server.server_round_flat(skey, buf, k)
        np.testing.assert_allclose(np.asarray(spec.flatten(out_tree)),
                                   np.asarray(out_flat), atol=1e-6)


class TestSpecAndConversion:
    def test_mixed_dtype_roundtrip(self):
        tree = {"w": jnp.ones((3, 4), jnp.bfloat16),
                "b": jnp.arange(3, dtype=jnp.float32),
                "s": jnp.asarray(2.0, jnp.float32)}
        spec = flat_lib.make_flat_spec(tree)
        assert spec.dtype == jnp.float32  # promoted
        assert spec.d == 12 + 3 + 1
        back = spec.unravel(spec.ravel(tree))
        assert back["w"].dtype == jnp.bfloat16
        assert back["s"].shape == ()
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)),
            back, tree)

    def test_fedstate_conversion_roundtrip(self, problem, spec):
        opt = optim.momentum_sgd()
        state = init_state(jnp.zeros(problem.d), problem.n, optimizer=opt)
        fstate = flat_lib.flatten_fedstate(spec, state)
        assert fstate.flat.shape == (problem.n, spec.d)
        back = flat_lib.unflatten_fedstate(spec, fstate)
        np.testing.assert_array_equal(np.asarray(back.params),
                                      np.asarray(state.params))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), back.opt_state, state.opt_state)

    def test_adamw_state_conversion(self, problem, spec):
        opt = optim.adamw()
        state = init_state(jnp.zeros(problem.d), problem.n, optimizer=opt)
        fstate = flat_lib.flatten_fedstate(spec, state)
        assert fstate.opt_state["m"].shape == (problem.n, spec.d)
        assert fstate.opt_state["count"].shape == ()
        back = flat_lib.unflatten_fedstate(spec, fstate)
        assert back.opt_state["count"].shape == (problem.n,)

    def test_opt_state_conversion_keeps_f32_moments(self):
        """bf16 parameter buffer: converted momentum stays f32, matching
        what init_flat_state's optimizer.init(flat) produces."""
        opt = optim.momentum_sgd()
        params = jnp.ones((7,), jnp.bfloat16)
        spec = flat_lib.make_flat_spec(params)
        assert spec.dtype == jnp.bfloat16
        state = init_state(params, 4, optimizer=opt)
        fstate = flat_lib.flatten_fedstate(spec, state)
        assert fstate.opt_state.dtype == jnp.float32
        fresh = flat_lib.init_flat_state(spec, params, 4, optimizer=opt)
        assert fresh.opt_state.dtype == fstate.opt_state.dtype

    def test_gossip_impl_validation_message(self, problem):
        cfg, _, _ = _setup(problem)
        with pytest.raises(ValueError, match="make_permute_gossip"):
            FedDecConfig(mixing=cfg.mixing, gossip_impl="permute")
        with pytest.raises(ValueError, match="dense|none|pallas|sparse"):
            FedDecConfig(mixing=cfg.mixing, gossip_impl="bogus")


def _reshape_flatten(spec, stacked, dtype):
    """The plain route: every leaf reshaped to (n, S) in place."""
    leaves = spec.treedef.flatten_up_to(stacked)
    return jnp.concatenate(
        [l.astype(dtype).reshape(l.shape[0], -1) for l in leaves], axis=1)


def _reshape_unflatten(spec, buf):
    n = buf.shape[0]
    return [buf[:, o:o + s].reshape((n,) + shape).astype(dt)
            for o, s, shape, dt in zip(spec.offsets, spec.sizes,
                                       spec.shapes, spec.dtypes)]


class TestLaneDenseRoute:
    """Leaves whose rows are not lane-aligned but whose per-agent size is
    move through FlatSpec's lane-dense swap; the buffer and the leaves are
    bit-identical to the plain reshape's."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n", [1, 2, 4, 8])
    @pytest.mark.parametrize("shape,routed", [
        ((32, 200), True), ((2, 16, 176), True),
        ((24, 200), False)])  # S = 4,800: not whole rows of 128 lanes
    def test_route_is_bit_identical(self, shape, routed, n, dtype):
        key = jax.random.key(n)
        tree = {"odd": jax.random.normal(key, (n,) + shape).astype(dtype),
                "aligned": jax.random.normal(jax.random.fold_in(key, 1),
                                             (n, 3, 256)),
                "bias": jnp.arange(n * 5, dtype=jnp.float32).reshape(n, 5)}
        spec = flat_lib.make_flat_spec_from_stacked(tree)
        odd = sorted(tree).index("odd")  # leaves in sorted-key order
        assert spec.lane_dense_leaves == ((odd,) if routed else ())
        buf = jax.jit(spec.flatten)(tree)
        np.testing.assert_array_equal(
            np.asarray(buf), np.asarray(_reshape_flatten(spec, tree,
                                                         spec.dtype)))
        back = jax.tree.leaves(jax.jit(spec.unflatten)(buf))
        for got, want, leaf in zip(back, _reshape_unflatten(spec, buf),
                                   jax.tree.leaves(tree)):
            assert got.dtype == leaf.dtype and got.shape == leaf.shape
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(leaf, np.float32))

    @pytest.mark.parametrize("name,cut,routed,share", [
        # the benchmark cells' cuts: Qwen's head (vocab 18,992) and
        # Mamba-2's in_proj (10,576 columns) take the route
        ("qwen1.5-4b", dict(num_layers=2, vocab_size=18_992),
         ["['head']['w']"], 0.1900),
        ("mamba2-2.7b", dict(num_layers=4, vocab_size=6_286),
         ["['stack']['scan']['sub_0']['mixer']['in_proj']['w']"], 0.6120),
        # Qwen's published vocabulary, 151,936, is lane-aligned: no leaf
        ("qwen1.5-4b", dict(num_layers=2), [], 0.0)])
    def test_route_picks_unaligned_leaves(self, name, cut, routed, share):
        import dataclasses

        from repro.configs import get_config
        from repro.models import build_model

        cfg = dataclasses.replace(get_config(name), **cut)
        shapes = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
        spec = flat_lib.make_flat_spec(shapes)
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(shapes)[0]]
        assert [paths[i] for i in spec.lane_dense_leaves] == routed
        assert spec.lane_dense_share == pytest.approx(share, abs=1e-4)
