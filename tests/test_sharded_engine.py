"""Sharded-engine contract tests: quotient-graph metadata, the sharded
gossip collective, launch lowering, and sharding persistence.

The sharded ≡ flat trajectory-equivalence grid (and its 8-device
subprocess twin) that used to live here moved to
tests/conformance/test_grid.py — one differential harness covering all
four engine lowerings against the single flat reference.

Two tiers remain:

  * host-side unit tests of the quotient-graph / cut-edge metadata and the
    sharded cost model — always run, no devices needed;
  * in-process contract tests that need a multi-device backend and **skip
    cleanly when fewer than 2 host devices are visible** (the CI
    ``multi-device`` job provides 8 via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedDecConfig
from repro.core import engine as engine_lib
from repro.core import flat as flat_lib
from repro.core import sharded, sweep as sweep_lib, topology as topo
from repro.core.mixing import MixingDistribution
from repro.launch import analysis

N_AGENTS = 8
H_CFG = 4
D = 37

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 host devices (XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


# ---------------------------------------------------------------------------
# Host-side metadata (no devices needed)
# ---------------------------------------------------------------------------


class TestQuotientGraph:
    def test_ring_quotient_is_ring(self):
        """ring(32, k=2) over 8 contiguous blocks of 4 collapses to a plain
        ring over shards: every cut edge reaches only the adjacent block."""
        q = sharded.quotient_graph(topo.ring_graph(32, k=2), 8)
        expect = topo.ring_graph(8, k=1)
        np.testing.assert_array_equal(q.adjacency, expect.adjacency)

    def test_one_agent_per_shard_is_identity(self):
        g = topo.geographic_graph(8, 0.7, seed=1)
        q = sharded.quotient_graph(g, 8)
        np.testing.assert_array_equal(q.adjacency, g.adjacency)

    def test_single_shard_has_no_edges(self):
        q = sharded.quotient_graph(topo.ring_graph(8, k=2), 1)
        assert q.n == 1 and q.num_edges == 0

    def test_indivisible_raises(self):
        with pytest.raises(ValueError, match="divide"):
            sharded.quotient_graph(topo.ring_graph(8), 3)

    def test_cut_edge_stats(self):
        g = topo.ring_graph(32, k=2)
        stats = sharded.cut_edge_stats(g, 8)
        assert stats["agents_per_shard"] == 4
        assert stats["num_directed_edges"] == 2 * g.num_edges
        # per block of 4 on a k=2 ring: 3 directed edges cross each side
        assert stats["num_cut_edges"] == 8 * 6
        assert stats["num_halo_rounds"] == 2  # quotient ring: left + right
        stats1 = sharded.cut_edge_stats(g, 1)
        assert stats1["num_cut_edges"] == 0
        assert stats1["num_halo_rounds"] == 0

    def test_sharded_cost_model_shape(self):
        stats = sharded.cut_edge_stats(topo.ring_graph(32, k=2), 8)
        model = analysis.sharded_gossip_cost_model(
            n_agents=32, d=1 << 16, n_shards=8,
            num_cut_edges=stats["num_cut_edges"],
            num_halo_rounds=stats["num_halo_rounds"])
        # the halo moves 2 blocks/device; dense psum_scatter ~ (s-1)/s · n·D
        assert model["sparse"]["collective_bytes"] \
            < model["dense"]["collective_bytes"]
        assert model["none"]["collective_bytes"] == 0.0
        assert model["sparse"]["ideal_cut_edge_bytes"] \
            <= model["sparse"]["collective_bytes"] * 8

    def test_engine_validates_divisibility(self):
        md = MixingDistribution(topo.ring_graph(8, k=2))
        cfg = FedDecConfig(mixing=md)
        spec = flat_lib.make_flat_spec(jnp.zeros(D))
        mesh = jax.make_mesh((len(jax.devices()),), ("agents",))
        if 8 % len(jax.devices()) == 0:
            pytest.skip("device count divides n_agents")
        with pytest.raises(ValueError, match="divisible"):
            sharded.make_sharded_feddec_step(
                cfg, spec, lambda p, b, k: (0.0, p), lambda t: 0.1, mesh)


# ---------------------------------------------------------------------------
# In-process contract tests (multi-device job)
# ---------------------------------------------------------------------------


def _grad_fn(p, batch, key):
    noise = jax.random.normal(key, p.shape) * 0.01
    return 0.5 * jnp.sum((p - batch) ** 2), (p - batch) + noise


def _lr(t):
    return jnp.asarray(0.05, jnp.float32)


def _setup(*, p_fail=0.0, gossip_impl="dense", server_enabled=True):
    g = topo.geographic_graph(N_AGENTS, 0.6, seed=3)
    md = MixingDistribution(g, p_fail=p_fail,
                            scheme="metropolis" if p_fail else "laplacian")
    return FedDecConfig(mixing=md, h=H_CFG, k=2, gossip_impl=gossip_impl,
                        server_enabled=server_enabled)


def _n_shards_for(agents_per_device: int) -> int:
    n_shards = N_AGENTS // agents_per_device
    if n_shards > len(jax.devices()):
        pytest.skip(f"needs {n_shards} devices")
    return n_shards


def _smoke_arch_round(n_runs: int):
    """(EngineSpec, fused 2-step round, abstract args) of the first
    sharded-layout smoke arch, one agent per host device: the round comes
    from ``engine.make_engine_round`` over the model's grad_fn and
    ``flat.make_flat_spec``; ``n_runs`` > 1 makes a seed lattice."""
    from repro.configs import ARCH_NAMES, get_config
    from repro.configs.base import FedConfig
    from repro.launch.mesh import make_agent_mesh
    from repro.launch.specs import concrete_batch
    from repro.launch.steps import build_fed_setup
    from repro.models import build_model
    from repro.sharding import MeshAxes

    cfg = next(get_config(a) for a in ARCH_NAMES
               if get_config(a).fed_agent_layout == "sharded").smoke()
    model = build_model(cfg)
    n = len(jax.devices())
    fcfg, _ = build_fed_setup(
        cfg, MeshAxes(("data",), "model", {"data": n, "model": 1}),
        FedConfig(n_agents=n, h=2))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    spec = flat_lib.make_flat_spec(shapes)
    espec = engine_lib.parse_engine_spec([fcfg] * n_runs, n_shards=n)
    round_fn = engine_lib.make_engine_round(
        espec, model.grad_fn(), _lr, flat_spec=spec,
        mesh=make_agent_mesh(n))
    if n_runs > 1:
        state = jax.eval_shape(
            lambda p: sweep_lib.init_sweep_state(espec.plan(), spec, p),
            shapes)
    else:
        state = jax.eval_shape(
            lambda p: flat_lib.init_flat_state(spec, p, n), shapes)
    lead = (2, n_runs) if n_runs > 1 else (2,)
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(lead + x.shape, x.dtype),
        jax.eval_shape(lambda: concrete_batch(cfg, n, 1, 16,
                                              jax.random.key(0))))
    keys = jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), n_runs)
        if n_runs > 1 else jax.random.key(0))
    return espec, round_fn, (state, batch, keys)


@multi_device
class TestShardedContract:
    def test_sharded_gossip_matches_dense(self):
        """make_sharded_gossip == unsharded einsum on a random failed-link
        W, for both halo and psum_scatter paths."""
        g = topo.geographic_graph(N_AGENTS, 0.7, seed=5)
        md = MixingDistribution(g, p_fail=0.3, scheme="metropolis")
        w = md.sample(jax.random.key(7))
        x = jax.random.normal(jax.random.key(1), (N_AGENTS, 64))
        ref = jnp.einsum("ij,jd->id", w, x,
                         precision=jax.lax.Precision.HIGHEST)
        n_shards = _n_shards_for(4)
        mesh = jax.make_mesh((n_shards,), ("agents",),
                             devices=jax.devices()[:n_shards])
        for impl in ("dense", "sparse"):
            cfg = FedDecConfig(mixing=md, gossip_impl=impl)
            got = jax.jit(sharded.make_sharded_gossip(cfg, mesh))(w, x)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=1e-5, err_msg=impl)

    def test_build_train_lowerable_sharded(self):
        """A real smoke arch's fused round, built from its grad_fn and flat
        spec as train_loop builds it, lowers and compiles through the
        sharded engine on the host mesh."""
        espec, round_fn, args = _smoke_arch_round(n_runs=1)
        lowered = round_fn.lower(*args)
        assert lowered.out_info[0].flat.shape == args[0].flat.shape
        lowered.compile()
        with pytest.raises(ValueError, match="mesh"):
            engine_lib.make_engine_round(
                espec, _grad_fn, _lr,
                flat_spec=flat_lib.make_flat_spec(jnp.zeros(D)))

    def test_build_train_lowerable_sharded_sweep(self):
        """The composed lowering: a 2-run lattice of the same round lowers
        the whole (R, n_local, D) lattice as ONE shard_map program."""
        _, round_fn, args = _smoke_arch_round(n_runs=2)
        lowered = round_fn.lower(*args)
        assert lowered.out_info[0].flat.shape[0] == 2
        lowered.compile()

    def test_state_stays_sharded(self):
        """The carried buffer remains block-sharded across round calls —
        no silent gather back to one device."""
        n_shards = _n_shards_for(4)
        cfg = _setup()
        spec = flat_lib.make_flat_spec(jnp.zeros(D))
        mesh = jax.make_mesh((n_shards,), ("agents",),
                             devices=jax.devices()[:n_shards])
        sh_round = sharded.make_sharded_feddec_round(cfg, spec, _grad_fn,
                                                     _lr, mesh, donate=True)
        state = sharded.shard_flat_state(
            flat_lib.init_flat_state(spec, jnp.zeros(D), N_AGENTS), mesh)
        batches = jax.random.normal(jax.random.key(2), (H_CFG, N_AGENTS, D))
        state, _ = sh_round(state, batches, jax.random.key(0))
        sharding = state.flat.sharding
        assert getattr(sharding, "spec", None) is not None
        assert sharding.spec[0] == "agents"
