"""2-D mesh contracts: make_fed_mesh, the (A, M) cost model, and the
HLO axis-separation classifier.

The mesh-shape contracts that need more than one device, and the
compiled-HLO axis assertions (gossip collectives over 'agents' only,
matmul/loss collectives over 'model' only), run in subprocesses that force
host devices themselves — the tier-1 single-device session still covers
them, and the override never leaks into this process.
"""

import os
import subprocess
import sys

import jax
import pytest

from repro.launch import hlo_analysis
from repro.launch.analysis import mesh2d_cost_model
from repro.launch.mesh import make_fed_mesh


# ---------------------------------------------------------------------------
# make_fed_mesh contracts (single-device tier)
# ---------------------------------------------------------------------------


class TestMakeFedMesh:
    def test_axis_names_and_shape(self):
        mesh = make_fed_mesh(1, 1)
        assert mesh.axis_names == ("agents", "model")
        assert dict(mesh.shape) == {"agents": 1, "model": 1}

    def test_default_model_axis_is_one(self):
        assert dict(make_fed_mesh(1).shape)["model"] == 1

    def test_custom_axis_names(self):
        mesh = make_fed_mesh(1, 1, agent_axis="a", model_axis="m")
        assert mesh.axis_names == ("a", "m")

    @pytest.mark.parametrize("a,m", [(0, 1), (1, 0), (-1, 1), (1, -2)])
    def test_rejects_nonpositive_shapes(self, a, m):
        with pytest.raises(ValueError):
            make_fed_mesh(a, m)

    def test_rejects_more_shards_than_devices(self):
        avail = len(jax.devices())
        with pytest.raises(ValueError, match="devices"):
            make_fed_mesh(avail + 1, 1)
        with pytest.raises(ValueError, match="devices"):
            make_fed_mesh(1, avail + 1)


_MESH_SHAPE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.launch.mesh import make_agent_mesh, make_fed_mesh

# row-major (A, M) layout: id = a * M + m — the invariant the HLO axis
# classifier (launch.hlo_analysis.collective_axes) decodes groups against
for a, m in [(4, 2), (2, 4), (8, 1), (1, 8), (2, 2)]:
    mesh = make_fed_mesh(a, m)
    assert mesh.axis_names == ("agents", "model")
    assert mesh.devices.shape == (a, m)
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    np.testing.assert_array_equal(
        ids, np.arange(a * m).reshape(a, m))

# make_fed_mesh(A, 1) is the agent mesh with a size-1 model axis appended:
# same devices, same order, and the 1-D engine lowers identically on it
for a in (2, 4, 8):
    fed = make_fed_mesh(a, 1)
    agent = make_agent_mesh(a)
    assert [d.id for d in fed.devices.ravel()] \
        == [d.id for d in agent.devices.ravel()]
    assert dict(fed.shape)["agents"] == dict(agent.shape)["agents"] == a

# A*M must fit the device count even when each factor alone would
try:
    make_fed_mesh(4, 4)
except ValueError as e:
    assert "devices" in str(e)
else:
    raise AssertionError("make_fed_mesh(4, 4) on 8 devices did not raise")
print("MESH_SHAPE_OK")
"""


def _run_subprocess(script: str, sentinel: str, timeout: int = 600) -> None:
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(os.path.join(here, "..", "src"))
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert res.returncode == 0, res.stderr
    assert sentinel in res.stdout, res.stdout


def test_mesh_shape_contracts_subprocess():
    """Row-major device layout + make_fed_mesh(A, 1) ≡ make_agent_mesh(A)
    under 8 forced host devices."""
    _run_subprocess(_MESH_SHAPE, "MESH_SHAPE_OK")


# ---------------------------------------------------------------------------
# mesh2d_cost_model: exact per-device byte accounting
# ---------------------------------------------------------------------------


class TestMesh2dCostModel:
    N, D = 64, 4096

    def model(self, a, m, halo=2):
        return mesh2d_cost_model(n_agents=self.N, d=self.D,
                                 n_agent_shards=a, n_model_shards=m,
                                 num_halo_rounds=halo)

    def test_state_bytes_exact(self):
        for a, m in [(1, 1), (4, 2), (2, 4), (8, 8)]:
            rec = self.model(a, m)
            for impl in ("dense", "sparse", "pallas", "none"):
                assert rec[impl]["state_bytes_per_device"] \
                    == self.N // a * (self.D // m) * 4

    def test_am_way_scaling(self):
        base = self.model(1, 1)["dense"]["state_bytes_per_device"]
        for a, m in [(2, 2), (4, 2), (8, 8)]:
            got = self.model(a, m)["dense"]["state_bytes_per_device"]
            assert got * a * m == base

    def test_dense_gossip_bytes(self):
        a, m = 4, 2
        rec = self.model(a, m)["dense"]
        assert rec["gossip_collective_bytes"] == pytest.approx(
            (a - 1) / a * self.N * (self.D // m) * 4)

    def test_halo_gossip_bytes(self):
        a, m, halo = 4, 2, 3
        rec = self.model(a, m, halo)["sparse"]
        assert rec["gossip_collective_bytes"] == pytest.approx(
            halo * (self.N // a) * (self.D // m) * 4)
        assert rec == self.model(a, m, halo)["pallas"]

    def test_model_axis_collective_bytes(self):
        a, m = 2, 4
        rec = self.model(a, m)["dense"]
        assert rec["model_collective_bytes"] == pytest.approx(
            2.0 * (m - 1) / m * (self.N // a) * 4)
        # M = 1 degenerates to the 1-D engine: no model-axis traffic
        assert self.model(4, 1)["dense"]["model_collective_bytes"] == 0.0

    def test_server_bytes(self):
        a, m = 4, 2
        rec = self.model(a, m)["dense"]
        assert rec["server_bytes_per_round"] == pytest.approx(
            2.0 * (a - 1) / a * (self.D // m) * 4)
        # single agent shard: the psum is device-local
        assert self.model(1, 4)["dense"]["server_bytes_per_round"] == 0.0

    def test_impl_none_has_no_gossip_traffic(self):
        rec = self.model(4, 2)["none"]
        assert rec["gossip_collective_bytes"] == 0.0


# ---------------------------------------------------------------------------
# HLO replica-group parsing + (A, M) axis classification
# ---------------------------------------------------------------------------


class TestReplicaGroupParsing:
    def test_literal(self):
        got = hlo_analysis._parse_replica_groups(
            "replica_groups={{0,2},{1,3}}", 4)
        assert got == [[0, 2], [1, 3]]

    def test_literal_empty_means_all_devices(self):
        got = hlo_analysis._parse_replica_groups("replica_groups={}", 4)
        assert got == [[0, 1, 2, 3]]

    def test_iota(self):
        got = hlo_analysis._parse_replica_groups(
            "replica_groups=[2,2]<=[4]", 4)
        assert got == [[0, 1], [2, 3]]

    def test_iota_transposed(self):
        got = hlo_analysis._parse_replica_groups(
            "replica_groups=[2,2]<=[2,2]T(1,0)", 4)
        assert got == [[0, 2], [1, 3]]

    def test_absent(self):
        assert hlo_analysis._parse_replica_groups("channel_id=1", 4) is None


class TestAxisClassification:
    def test_groups_model_only(self):
        # (A, M) = (2, 2): ids {0,1} and {2,3} each fix id // M
        assert hlo_analysis._axis_of_groups([[0, 1], [2, 3]], 2) == "model"

    def test_groups_agents_only(self):
        assert hlo_analysis._axis_of_groups([[0, 2], [1, 3]], 2) == "agents"

    def test_groups_mixed(self):
        assert hlo_analysis._axis_of_groups([[0, 3]], 2) == "mixed"
        assert hlo_analysis._axis_of_groups([[0, 1], [0, 2]], 2) == "mixed"

    def test_groups_singletons(self):
        assert hlo_analysis._axis_of_groups([[0], [1]], 2) == "single"

    def test_m1_degenerates_to_agents(self):
        assert hlo_analysis._axis_of_groups([[0, 1, 2, 3]], 1) == "agents"

    def test_a1_degenerates_to_model(self):
        assert hlo_analysis._axis_of_groups([[0, 1, 2, 3]], 4) == "model"

    def test_pairs(self):
        agents = [(0, 2), (2, 0), (1, 3), (3, 1)]
        assert hlo_analysis._axis_of_pairs(agents, 2) == "agents"
        assert hlo_analysis._axis_of_pairs([(0, 1), (1, 0)], 2) == "model"
        assert hlo_analysis._axis_of_pairs([(0, 3)], 2) == "mixed"
        assert hlo_analysis._axis_of_pairs([(0, 0)], 2) == "single"


_SYNTHETIC_HLO = """
HloModule synth

ENTRY %main (p0: f32[4,8]) -> f32[2,8] {
  %p0 = f32[4,8] parameter(0)
  %ar0 = f32[4,8] all-reduce(%p0), replica_groups={{0,1},{2,3}}, to_apply=%add, metadata={op_name="jit(f)/psum[axes=('model',)]"}
  %cp0 = f32[4,8] collective-permute(%ar0), source_target_pairs={{0,2},{2,0},{1,3},{3,1}}
  ROOT %rs0 = f32[2,8] reduce-scatter(%cp0), replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}, to_apply=%add
}
"""


class TestCollectiveAxesOnText:
    def test_classifies_synthetic_module(self):
        colls = hlo_analysis.collective_axes(_SYNTHETIC_HLO, 2, 2)
        by_kind = {c.kind: c for c in colls}
        assert by_kind["all-reduce"].axis == "model"
        assert by_kind["collective-permute"].axis == "agents"
        assert by_kind["reduce-scatter"].axis == "agents"
        assert by_kind["all-reduce"].groups == [[0, 1], [2, 3]]
        assert by_kind["collective-permute"].pairs == [
            (0, 2), (2, 0), (1, 3), (3, 1)]

    def test_axis_separation_summary(self):
        sep = hlo_analysis.axis_separation(_SYNTHETIC_HLO, 2, 2)
        assert sep == {"model": ["all-reduce"],
                       "agents": ["collective-permute", "reduce-scatter"]}


# ---------------------------------------------------------------------------
# THE tentpole assertion: compiled-HLO axis separation of the 2-D engine
# ---------------------------------------------------------------------------


_HLO_AXES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.core import flat as flat_lib, sharded, topology as topo
from repro.core.feddec import FedDecConfig
from repro.core.mixing import MixingDistribution
from repro.data import linreg
from repro.launch import hlo_analysis as ha
from repro.launch.mesh import make_fed_mesh

N, D, A, M = 8, 256, 2, 2
prob = linreg.make_problem(n=N, d=D, seed=0, c_base=1.3)
grad_fn = linreg.make_grad_fn(prob.m_rows)
lr = lambda t: jnp.asarray(0.05, jnp.float32)
spec = flat_lib.make_flat_spec(jnp.zeros(prob.d))
g = topo.geographic_graph(N, 0.6, seed=3)
md = MixingDistribution(g, p_fail=0.0, scheme="laplacian")
keys = jax.random.split(jax.random.key(11), 4)
batches = jax.vmap(lambda k: linreg.sample_minibatch(prob, k, m=1))(keys)

for impl in ("pallas", "dense"):
    cfg = FedDecConfig(mixing=md, h=4, k=2, server_enabled=True,
                       gossip_impl=impl)
    mesh = make_fed_mesh(A, M)
    rnd = sharded.make_sharded_feddec_round(cfg, spec, grad_fn, lr, mesh,
                                            model_axis="model", jit=True)
    st = flat_lib.init_flat_state(spec, jnp.zeros(prob.d), N)
    st = sharded.shard_flat_state(st, mesh, model_axis="model")
    text = jax.jit(rnd).lower(st, batches, jax.random.key(5)) \
        .compile().as_text()
    sep = ha.axis_separation(text, A, M)
    # the separation contract: NO collective mixes the two mesh axes
    assert "mixed" not in sep, (impl, sep)
    assert "unknown" not in sep, (impl, sep)
    # gossip + server traffic lives on the agent axis only ...
    assert "agents" in sep, (impl, sep)
    colls = ha.collective_axes(text, A, M)
    gossip_kinds = ("collective-permute", "reduce-scatter", "all-to-all")
    for c in colls:
        if c.kind in gossip_kinds:
            assert c.axis == "agents", (impl, c)
    # ... and the model axis carries only element-count reductions
    # (loss/matmul all-reduce), never agent-exchange collectives
    model_kinds = set(sep.get("model", ()))
    assert not model_kinds & set(gossip_kinds), (impl, sep)
    if impl == "pallas":
        perms = [c for c in colls if c.kind == "collective-permute"]
        assert perms, "ppermute halo missing from the pallas lowering"
        for c in perms:
            assert all(s % M == t % M for s, t in c.pairs), c
print("HLO_AXES_OK")
"""


def test_hlo_axis_separation_subprocess():
    """Compile the 2-D round at (A, M) = (2, 2) and assert from the
    optimized HLO that gossip collectives carry only the 'agents' axis and
    model-axis collectives never exchange agent state — the ISSUE's
    axis-separation acceptance criterion, checked, not eyeballed."""
    _run_subprocess(_HLO_AXES, "HLO_AXES_OK")


# ---------------------------------------------------------------------------
# mesh-matrix CI cell: one (A, M) shape per job, driven by env
# ---------------------------------------------------------------------------


_MATRIX_CELL = r"""
import os
A = int(os.environ.get("MESH_CELL_A", "2"))
M = int(os.environ.get("MESH_CELL_M", "2"))
NDEV = int(os.environ.get("MESH_CELL_DEVICES", "8"))
assert A * M <= NDEV, (A, M, NDEV)
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={NDEV}")
import jax, jax.numpy as jnp
import numpy as np
from repro.core import flat as flat_lib, sharded, topology as topo
from repro.core.feddec import FedDecConfig
from repro.core.mixing import MixingDistribution
from repro.launch import hlo_analysis as ha
from repro.launch.mesh import make_fed_mesh

# N divisible by every A <= 16, D by every M <= 16
N, D, H = 16, 256, 2
graph = topo.ring_graph(N, k=2)
md = MixingDistribution(graph, scheme="metropolis")
spec = flat_lib.make_flat_spec(jnp.zeros(D))

def grad_fn(p, batch, key):
    del key
    return 0.5 * jnp.sum((p - batch) ** 2), p - batch

lr = lambda t: jnp.asarray(0.05, jnp.float32)
batches = jax.random.normal(jax.random.key(3), (H, N, D), jnp.float32)
key = jax.random.key(4)
gossip_kinds = ("collective-permute", "reduce-scatter", "all-to-all")

for impl in ("dense", "sparse"):
    cfg = FedDecConfig(mixing=md, h=H, k=2, gossip_impl=impl)
    ref_state, ref_m = flat_lib.make_flat_feddec_round(
        cfg, spec, grad_fn, lr, donate=False)(
        flat_lib.init_flat_state(spec, jnp.zeros(D), N), batches, key)
    mesh = make_fed_mesh(A, M)
    rnd = sharded.make_sharded_feddec_round(
        cfg, spec, grad_fn, lr, mesh, donate=False, model_axis="model")
    st = sharded.shard_flat_state(
        flat_lib.init_flat_state(spec, jnp.zeros(D), N), mesh,
        model_axis="model")
    # the cell's trajectory matches the single-device flat reference
    out_state, out_m = rnd(st, batches, key)
    np.testing.assert_allclose(np.asarray(out_state.flat),
                               np.asarray(ref_state.flat),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out_m["loss"]),
                               np.asarray(ref_m["loss"]),
                               atol=1e-5, rtol=1e-5)
    # per-device state is exactly the n/A x D/M block
    assert out_state.flat.addressable_shards[0].data.nbytes \
        == N // A * (D // M) * 4
    # HLO axis separation holds at THIS cell's (A, M)
    text = jax.jit(rnd).lower(st, batches, key).compile().as_text()
    sep = ha.axis_separation(text, A, M)
    assert "mixed" not in sep, (impl, sep)
    assert "unknown" not in sep, (impl, sep)
    for c in ha.collective_axes(text, A, M):
        if c.kind in gossip_kinds:
            assert c.axis == "agents", (impl, c)
    assert not set(sep.get("model", ())) & set(gossip_kinds), (impl, sep)
print(f"MATRIX_CELL_OK a={A} m={M}")
"""


def test_mesh_matrix_cell_subprocess():
    """One mesh-matrix cell: equivalence vs the flat reference, exact
    per-device shard bytes, and HLO axis separation at the (A, M) shape
    given by MESH_CELL_A / MESH_CELL_M (defaults (2, 2) for tier-1; the
    CI mesh-matrix lane sets one shape per job under 16 forced devices)."""
    a = int(os.environ.get("MESH_CELL_A", "2"))
    m = int(os.environ.get("MESH_CELL_M", "2"))
    _run_subprocess(_MATRIX_CELL, f"MATRIX_CELL_OK a={a} m={m}")
