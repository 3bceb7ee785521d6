"""Fused update+gossip kernels (kernels/update_mix.py) and the
``fuse_update_mix`` engine axis.

Four tiers, mirroring tests/test_compress.py's layout:

  * kernel equivalence (interpret mode off-TPU): every fused wrapper in
    kernels/ops.py — dense / sparse-ELL / batched, sgd / momentum /
    nesterov, and the EF ``ef_mix`` family — against the unfused two-pass
    XLA composition, across f32/bf16, non-block_d-aligned D (padding) and
    uneven-degree graphs (ELL degree padding);
  * the block_d VMEM budget rule, its REPRO_BLOCK_D override, the budget
    width's bit identity with a 2048-lane tile, and the backend rule for
    interpret mode;
  * engine-level trajectories: ``fuse_update_mix=True`` matches the
    unfused flat/sweep engines to 1e-5 across impls × sgd/momentum ×
    codec on/off; adamw (no fused kernel) falls back bit-identically;
  * spec validation + the donation regression: executors built with
    ``donate=True`` must not emit XLA "buffer donation" warnings for the
    flat / sweep / sharded layouts (subprocess, 8 forced host devices).

The fused-vs-unfused cost model (analysis.roundfuse_cost_model) and the
sharded boundary/interior split (sharded.boundary_row_split) are unit
tested here too.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import FedDecConfig, engine
from repro.core import flat as flat_lib
from repro.core import sharded, sweep as sweep_lib
from repro.core import topology as topo
from repro.core.mixing import MixingDistribution
from repro.kernels import ops as kernel_ops
from repro.launch import analysis

N = 8
D = 37          # deliberately unaligned: every block_d pads
T_RUN = 6


def _w(n=N, seed=0, graph=None):
    g = graph or topo.geographic_graph(n, 0.6, seed=3)
    md = MixingDistribution(g, scheme="laplacian")
    return g, jnp.asarray(md.sample(jax.random.key(seed)), jnp.float32)


def _rand(shape, seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), shape).astype(dtype)


def _ref_update(x, g, eta, m=None, beta=None, nesterov=False):
    """The unfused two-pass body the kernels must reproduce."""
    if m is None:
        return x - jnp.asarray(eta, x.dtype) * g, None
    new_m = beta * m + g.astype(jnp.float32)
    d = beta * new_m + g.astype(jnp.float32) if nesterov else new_m
    return x - jnp.asarray(eta, x.dtype) * d.astype(x.dtype), new_m


def _ref_mix(w, p):
    return jnp.einsum("ij,jd->id", w, p.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST).astype(p.dtype)


# ---------------------------------------------------------------------------
# kernel equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [D, 515])
def test_update_mix_dense_sgd(dtype, d):
    _, w = _w()
    x, g = _rand((N, d), 1, dtype), _rand((N, d), 2, dtype)
    y = kernel_ops.update_mix(w, x, g, 0.05)
    p, _ = _ref_update(x, g, 0.05)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, jnp.float32),
                               np.asarray(_ref_mix(w, p), jnp.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("nesterov", [False, True])
def test_update_mix_dense_momentum(nesterov):
    _, w = _w()
    x, g, m = _rand((N, D), 1), _rand((N, D), 2), _rand((N, D), 3)
    y, new_m = kernel_ops.update_mix(w, x, g, 0.05, m=m, beta=0.9,
                                     nesterov=nesterov)
    p, ref_m = _ref_update(x, g, 0.05, m=m, beta=0.9, nesterov=nesterov)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_ref_mix(w, p)),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_m), np.asarray(ref_m),
                               atol=1e-6, rtol=1e-6)


def test_update_mix_batched_matches_per_run():
    r = 3
    _, w0 = _w(seed=0)
    _, w1 = _w(seed=1)
    _, w2 = _w(seed=2)
    w = jnp.stack([w0, w1, w2])
    x, g = _rand((r, N, D), 1), _rand((r, N, D), 2)
    eta = jnp.asarray([0.05, 0.1, 0.02], jnp.float32)
    y = kernel_ops.update_mix_batched(w, x, g, eta)
    for i in range(r):
        yi = kernel_ops.update_mix(w[i], x[i], g[i], eta[i])
        np.testing.assert_allclose(np.asarray(y[i]), np.asarray(yi),
                                   atol=1e-6, rtol=1e-6)


def test_update_mix_batched_momentum():
    r = 2
    _, w0 = _w(seed=0)
    _, w1 = _w(seed=1)
    w = jnp.stack([w0, w1])
    x, g, m = _rand((r, N, D), 1), _rand((r, N, D), 2), _rand((r, N, D), 3)
    eta = jnp.asarray([0.05, 0.1], jnp.float32)
    y, new_m = kernel_ops.update_mix_batched(w, x, g, eta, m=m, beta=0.9)
    for i in range(r):
        p, ref_m = _ref_update(x[i], g[i], eta[i], m=m[i], beta=0.9)
        np.testing.assert_allclose(np.asarray(y[i]),
                                   np.asarray(_ref_mix(w[i], p)),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(new_m[i]), np.asarray(ref_m),
                                   atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("graph_kind", ["ring", "geographic"])
def test_sparse_update_mix(graph_kind):
    """ELL path: uneven degrees (geographic) exercise the degree padding."""
    if graph_kind == "ring":
        graph = topo.ring_graph(N, k=2)
    else:
        graph = topo.geographic_graph(N, 0.6, seed=3)
    _, w = _w(graph=graph)
    x, g = _rand((N, D), 1), _rand((N, D), 2)
    fused = kernel_ops.make_sparse_update_mix_pallas(graph)
    y = fused(w, x, g, 0.05)
    p, _ = _ref_update(x, g, 0.05)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_ref_mix(w, p)),
                               atol=1e-6, rtol=1e-6)


def test_sparse_update_mix_momentum_batched():
    graphs = [topo.ring_graph(N, k=2), topo.geographic_graph(N, 0.6, seed=3)]
    ws = jnp.stack([_w(graph=g, seed=i)[1] for i, g in enumerate(graphs)])
    x, g = _rand((2, N, D), 1), _rand((2, N, D), 2)
    m = _rand((2, N, D), 3)
    eta = jnp.asarray([0.05, 0.1], jnp.float32)
    fused = kernel_ops.make_sparse_update_mix_batched_pallas(graphs, beta=0.9)
    y, new_m = fused(ws, x, g, eta, m)
    for i in range(2):
        p, ref_m = _ref_update(x[i], g[i], eta[i], m=m[i], beta=0.9)
        np.testing.assert_allclose(np.asarray(y[i]),
                                   np.asarray(_ref_mix(ws[i], p)),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(new_m[i]), np.asarray(ref_m),
                                   atol=1e-6, rtol=1e-6)


def _ref_ef(w, p, s, u):
    y = _ref_mix(w, s) + jnp.diagonal(w)[:, None] * (p - s)
    return y, u - s


def test_ef_mix_dense_and_sparse():
    graph = topo.geographic_graph(N, 0.6, seed=3)
    _, w = _w(graph=graph)
    p, s, u = _rand((N, D), 1), _rand((N, D), 2), _rand((N, D), 3)
    ref_y, ref_res = _ref_ef(w, p, s, u)
    y, res = kernel_ops.ef_mix(w, p, s, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(res), np.asarray(ref_res),
                               atol=1e-6, rtol=1e-6)
    ef = kernel_ops.make_sparse_ef_mix_pallas(graph)
    y2, res2 = ef(w, p, s, u)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(ref_y),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(res2), np.asarray(ref_res),
                               atol=1e-6, rtol=1e-6)


def test_ef_mix_batched():
    graphs = [topo.ring_graph(N, k=2), topo.geographic_graph(N, 0.6, seed=3)]
    ws = jnp.stack([_w(graph=g, seed=i)[1] for i, g in enumerate(graphs)])
    p, s, u = _rand((2, N, D), 1), _rand((2, N, D), 2), _rand((2, N, D), 3)
    y, res = kernel_ops.ef_mix_batched(ws, p, s, u)
    ef = kernel_ops.make_sparse_ef_mix_batched_pallas(graphs)
    y2, res2 = ef(ws, p, s, u)
    for i in range(2):
        ref_y, ref_res = _ref_ef(ws[i], p[i], s[i], u[i])
        for got in (y[i], y2[i]):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref_y),
                                       atol=1e-6, rtol=1e-6)
        for got in (res[i], res2[i]):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref_res),
                                       atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# block_d autotune + env overrides
# ---------------------------------------------------------------------------


_F32, _BF16 = jnp.float32, jnp.bfloat16


# (rows, d, dtype, streams of that dtype, f32 streams, f32 scratch tiles)
# -> the width the VMEM budget gives
@pytest.mark.parametrize("rows,d,dtype,streams,f32_streams,scratch,width", [
    # the benchmark cell's fused SGD update+mix: x, g in, y out
    (4, 255_864_320, _F32, 3, 0, 0, 32768),
    # momentum streams the f32 slot in and out as well
    (4, 255_864_320, _F32, 3, 2, 0, 30208),
    # 64 agents: the budget, not the cap, sets the width
    (64, 1 << 30, _F32, 3, 0, 0, 5376),
    # ... and bf16 rows are half the bytes, so twice the lanes
    (64, 1 << 30, _BF16, 3, 0, 0, 10880),
    # 1024 agents on an ELL kernel: one (1024, 2048) f32 tile alone was
    # 8 MiB
    (1024, 1 << 24, _F32, 3, 0, 2, 256),
    # a small D clamps to its 128-lane cover
    (4, 1000, _F32, 3, 0, 0, 1024),
])
def test_autotune_block_d_budget(rows, d, dtype, streams, f32_streams,
                                 scratch, width):
    x = jax.ShapeDtypeStruct((rows, d), dtype)
    got = kernel_ops._resolve_block_d(None, x, streams, scratch=scratch,
                                      f32_streams=f32_streams)
    assert got == width
    dtypes = (dtype,) * streams + (_F32,) * f32_streams
    assert kernel_ops.block_d_vmem_bytes(
        got, rows, dtypes, scratch=scratch) <= kernel_ops._VMEM_BUDGET
    if dtype == _BF16:
        f32_width = kernel_ops.autotune_block_d(rows, (_F32,) * streams,
                                                scratch=scratch)
        assert got >= 2 * f32_width
        assert kernel_ops.block_d_vmem_bytes(
            2 * f32_width, rows, dtypes, scratch=scratch) == \
            kernel_ops.block_d_vmem_bytes(f32_width, rows, (_F32,) * streams,
                                          scratch=scratch)


def test_autotune_block_d_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_BLOCK_D", "128")
    assert kernel_ops.autotune_block_d(4, (jnp.float32,) * 3) == 128


@pytest.mark.parametrize("data", ["exact", "random"])
def test_update_mix_budget_width_bit_identical(data):
    """The budget's wide tile changes no bit of y: every output column is
    the same contraction with W whatever the tile width.  D is a multiple
    of neither width, so both ragged last tiles are covered.

    ``exact``: a lazy-ring W (1/2, 1/4, 1/4), x and g on a 1/8 grid and
    eta 1/2, so every sum is exact in f32 and y is the same bits in any
    summation order, here as on the chip.  ``random``: on the chip the
    MXU's y is bit-identical too, but interpret mode hands the tile's dot
    to XLA:CPU, whose summation order depends on the tile's width: y may
    then differ by the rounding of a 4-term sum, never by more."""
    d = 70_001
    if data == "exact":
        w = 0.5 * jnp.eye(4) + 0.25 * (jnp.roll(jnp.eye(4), 1, 0)
                                       + jnp.roll(jnp.eye(4), -1, 0))
        x, g = (jax.random.randint(jax.random.key(k), (4, d), -64, 64)
                .astype(jnp.float32) / 8 for k in (1, 2))
        eta = 0.5
    else:
        w = jnp.asarray(MixingDistribution(
            topo.ring_graph(4, k=1), scheme="metropolis").sample(
                jax.random.key(0)), jnp.float32)
        x, g = _rand((4, d), 1), _rand((4, d), 2)
        eta = 0.05
    width = kernel_ops._resolve_block_d(None, x, 3)
    assert width > 2048 and d % width and d % 2048
    y = np.asarray(kernel_ops.update_mix(w, x, g, eta))
    ref = np.asarray(kernel_ops.update_mix(w, x, g, eta, block_d=2048))
    if data == "exact":
        np.testing.assert_array_equal(y, ref)
    else:
        terms = np.abs(np.asarray(w)) @ np.abs(np.asarray(x - eta * g))
        assert np.all(np.abs(y - ref) <= 4 * np.finfo(np.float32).eps * terms)


def test_interpret_env_override(monkeypatch):
    """Only the backend decides interpret mode: no environment variable
    can send a kernel to the interpreter on the chip, or compile it off
    the chip."""
    assert kernel_ops._interpret() is (jax.default_backend() != "tpu")
    for env in ("0", "1"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", env)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert kernel_ops._interpret() is False
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert kernel_ops._interpret() is True


# ---------------------------------------------------------------------------
# engine-level fused-vs-unfused trajectories
# ---------------------------------------------------------------------------


def _grad_fn(p, batch, key):
    noise = jax.random.normal(key, p.shape) * 0.01
    return 0.5 * jnp.sum((p - batch) ** 2), (p - batch) + noise


def _lr(t):
    return jnp.asarray(0.05, jnp.float32)


def _flat_cfg(impl, compress="none"):
    g = topo.geographic_graph(N, 0.6, seed=3)
    md = MixingDistribution(g, scheme="laplacian")
    return FedDecConfig(mixing=md, h=3, k=2, gossip_impl=impl,
                        gossip_compress=compress)


def _run_flat(cfg, opt, compress, fused):
    spec = flat_lib.make_flat_spec(jnp.zeros(D))
    round_fn = flat_lib.make_flat_feddec_round(
        cfg, spec, _grad_fn, _lr, optimizer=opt, donate=False,
        fuse_update_mix=fused)
    state = flat_lib.init_flat_state(spec, jnp.zeros(D), N, optimizer=opt,
                                     compress=compress)
    batches = _rand((T_RUN, N, D), 7)
    out, metrics = round_fn(state, batches, jax.random.key(5))
    return np.asarray(out.flat), np.asarray(metrics["loss"])


@pytest.mark.parametrize("compress", ["none", "int8"])
@pytest.mark.parametrize("opt_name", ["sgd", "momentum", "nesterov"])
@pytest.mark.parametrize("impl", ["dense", "pallas", "sparse"])
def test_flat_fused_matches_unfused(impl, opt_name, compress):
    opts = {"sgd": optim.sgd, "momentum": lambda: optim.momentum_sgd(0.9),
            "nesterov": lambda: optim.momentum_sgd(0.9, nesterov=True)}
    cfg = _flat_cfg(impl, compress)
    flat_u, loss_u = _run_flat(cfg, opts[opt_name](), compress, False)
    flat_f, loss_f = _run_flat(cfg, opts[opt_name](), compress, True)
    np.testing.assert_allclose(flat_f, flat_u, atol=1e-5)
    np.testing.assert_allclose(loss_f, loss_u, atol=1e-5)


def test_flat_adamw_falls_back_bit_identical():
    """No fused adamw kernel: the flag must be a no-op, bit for bit."""
    cfg = _flat_cfg("dense")
    flat_u, loss_u = _run_flat(cfg, optim.adamw(), "none", False)
    flat_f, loss_f = _run_flat(cfg, optim.adamw(), "none", True)
    np.testing.assert_array_equal(flat_f, flat_u)
    np.testing.assert_array_equal(loss_f, loss_u)


def test_custom_gossip_falls_back_bit_identical():
    """A caller-supplied gossip_fn can't be fused — flag must be a no-op."""
    cfg = _flat_cfg("dense")
    spec = flat_lib.make_flat_spec(jnp.zeros(D))
    gossip_fn = lambda w, p: _ref_mix(w, p)  # noqa: E731
    outs = []
    for fused in (False, True):
        round_fn = flat_lib.make_flat_feddec_round(
            cfg, spec, _grad_fn, _lr, gossip_fn=gossip_fn, donate=False,
            fuse_update_mix=fused)
        state = flat_lib.init_flat_state(spec, jnp.zeros(D), N)
        out, _ = round_fn(state, _rand((T_RUN, N, D), 7), jax.random.key(5))
        outs.append(np.asarray(out.flat))
    np.testing.assert_array_equal(outs[1], outs[0])


@pytest.mark.parametrize("impl", ["dense", "sparse"])
def test_sweep_fused_matches_unfused(impl):
    """Batched (R, n, D) fused path, including a FedAvg 'none' member."""
    g0 = topo.geographic_graph(N, 0.6, seed=3)
    g1 = topo.ring_graph(N, k=2)
    cfgs = [FedDecConfig(mixing=MixingDistribution(g0, scheme="laplacian"),
                         h=3, k=2, gossip_impl=impl),
            FedDecConfig(mixing=MixingDistribution(g1, scheme="metropolis"),
                         h=3, k=2, gossip_impl=impl),
            FedDecConfig(mixing=MixingDistribution(g1, scheme="metropolis"),
                         h=3, k=2, gossip_impl="none")]
    plan = sweep_lib.make_sweep_plan(cfgs)
    spec = flat_lib.make_flat_spec(jnp.zeros(D))
    batches = _rand((T_RUN, 3, N, D), 7)
    finals = {}
    for fused in (False, True):
        round_fn = sweep_lib.make_sweep_feddec_round(
            plan, spec, _grad_fn, _lr, donate=False, fuse_update_mix=fused)
        state = sweep_lib.init_sweep_state(plan, spec, jnp.zeros(D))
        out, _ = round_fn(state, batches,
                          jax.random.split(jax.random.key(5), 3))
        finals[fused] = np.asarray(out.flat)
    np.testing.assert_allclose(finals[True], finals[False], atol=1e-5)


# ---------------------------------------------------------------------------
# spec validation + cost model + boundary split
# ---------------------------------------------------------------------------


def test_parse_engine_spec_rejects_tree_layout():
    with pytest.raises(ValueError, match="flat .n, D. buffer layout"):
        engine.parse_engine_spec(_flat_cfg("dense"), layout="tree",
                                 fuse_update_mix=True)


def test_parse_engine_spec_rejects_sharding():
    with pytest.raises(ValueError, match="single-device"):
        engine.parse_engine_spec(_flat_cfg("sparse"), layout="flat",
                                 n_shards=4, fuse_update_mix=True)


def test_roundfuse_cost_model():
    sgd = analysis.roundfuse_cost_model(n_agents=N, d=D, optimizer="sgd")
    assert (sgd["passes_unfused"], sgd["passes_fused"]) == (5, 3)
    assert sgd["pass_ratio"] == 0.6
    assert sgd["unfused_pass_bytes"] == 5 * N * D * 4
    mom = analysis.roundfuse_cost_model(n_agents=N, d=D,
                                        optimizer="momentum")
    assert (mom["passes_unfused"], mom["passes_fused"]) == (7, 5)
    ef = analysis.roundfuse_cost_model(n_agents=N, d=D, optimizer="sgd",
                                       codec=True)
    assert (ef["passes_unfused"], ef["passes_fused"]) == (17, 13)
    with pytest.raises(ValueError, match="sgd|momentum"):
        analysis.roundfuse_cost_model(n_agents=N, d=D, optimizer="adamw")
    sh = analysis.roundfuse_cost_model(
        n_agents=64, d=256, optimizer="sgd", n_shards=8,
        boundary_rows_per_shard=4, num_halo_rounds=2)
    assert sh["interior_rows_per_shard"] == 4
    assert sh["halo_bytes_boundary"] == 2 * 4 * 256 * 4
    assert sh["halo_payload_ratio"] == 0.5
    assert 0.0 < sh["predicted_overlap_fraction"] <= 1.0


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_boundary_row_split(n_shards):
    graph = topo.ring_graph(64, k=2)
    split = sharded.boundary_row_split(graph, n_shards)
    n_local = 64 // n_shards
    adj = np.asarray(graph.adjacency)
    sym = adj | adj.T
    shard_of = np.arange(64) // n_local
    cross = sym & (shard_of[:, None] != shard_of[None, :])
    want_boundary = cross.any(axis=1)
    for s in range(n_shards):
        rows = split["index"][s][split["valid"][s]]
        got = np.zeros(64, bool)
        got[s * n_local + rows] = True
        np.testing.assert_array_equal(
            got, want_boundary & (shard_of == s),
            err_msg=f"shard {s} boundary rows wrong")
        assert split["counts"][s] == (want_boundary
                                      & (shard_of == s)).sum()
    assert split["b_max"] == split["counts"].max()
    assert split["interior_min"] == n_local - split["b_max"]


def test_boundary_row_split_fully_connected():
    """Every row on a cut edge: boundary == whole block, interior empty."""
    split = sharded.boundary_row_split(topo.fully_connected_graph(16), 4)
    assert split["b_max"] == 4 and split["interior_min"] == 0
    assert bool(split["valid"].all())


# ---------------------------------------------------------------------------
# donation regression (subprocess: 8 forced host devices)
# ---------------------------------------------------------------------------


_DONATION_SCRIPT = r"""
import warnings
warnings.simplefilter("always")
import jax, jax.numpy as jnp
from repro import optim
from repro.core import FedDecConfig, flat as flat_lib
from repro.core import sharded, sweep as sweep_lib, topology as topo
from repro.core.mixing import MixingDistribution
from repro.launch.mesh import make_agent_mesh

N, D, T = 8, 37, 3
g = topo.ring_graph(N, k=2)
md = MixingDistribution(g, scheme="metropolis")
cfg = FedDecConfig(mixing=md, h=T, k=2, gossip_impl="sparse")
spec = flat_lib.make_flat_spec(jnp.zeros(D))
grad_fn = lambda p, b, k: (0.5 * jnp.sum((p - b) ** 2), p - b)
lr = lambda t: jnp.asarray(0.05, jnp.float32)
batches = jax.random.normal(jax.random.key(3), (T, N, D), jnp.float32)
key = jax.random.key(4)

for fused in (False, True):
    fn = flat_lib.make_flat_feddec_round(cfg, spec, grad_fn, lr, donate=True,
                                         fuse_update_mix=fused)
    s = flat_lib.init_flat_state(spec, jnp.zeros(D), N)
    s, _ = fn(s, batches, key)
    s, _ = fn(s, batches, key)   # donated carry round-trips

plan = sweep_lib.make_sweep_plan([cfg, cfg])
fn = sweep_lib.make_sweep_feddec_round(plan, spec, grad_fn, lr, donate=True,
                                       fuse_update_mix=True)
s = sweep_lib.init_sweep_state(plan, spec, jnp.zeros(D))
b2 = jax.random.normal(jax.random.key(5), (T, 2, N, D), jnp.float32)
keys2 = jax.random.split(key, 2)
s, _ = fn(s, b2, keys2)
s, _ = fn(s, b2, keys2)

mesh = make_agent_mesh(8)
fn = sharded.make_sharded_feddec_round(cfg, spec, grad_fn, lr, mesh,
                                       donate=True)
s = sharded.shard_flat_state(flat_lib.init_flat_state(spec, jnp.zeros(D), N),
                             mesh)
s, _ = fn(s, batches, key)
s, _ = fn(s, batches, key)
print("DONATION_OK")
"""


def test_executors_use_donated_buffers_subprocess():
    """donate=True executors must actually consume their donation — an XLA
    "buffer donation requested ... not used" warning is a perf regression
    (the (n, D) carry silently double-buffers)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", "")).strip()
    res = subprocess.run([sys.executable, "-c", _DONATION_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr
    assert "DONATION_OK" in res.stdout
    offenders = [ln for ln in res.stderr.splitlines()
                 if "donat" in ln.lower()]
    assert not offenders, "\n".join(offenders)
