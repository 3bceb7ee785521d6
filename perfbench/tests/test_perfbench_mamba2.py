"""The program's Mamba-2 against the plain reference (references/mamba2.py),
its chunked scan against the recurrence it computes, the scan at the
published chunk of 256, the embedding scale flag, and the reader of
``ssd_scan_pct``.  All in float32 at tiny widths on the CPU."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, scopes
from perfbench.algorithm1 import MATMULS
from perfbench.seeds import purpose_key
from perfbench.spec import HERE, arch_config, load_cell, load_module
from perfbench.tests.test_perfbench_trace import MS, _ctx
from perfbench.tests.tiny import tiny_cell
from perfbench.trace import Op, Trace
from perfbench.weights import leaf_paths, make_weights

CELL = "mamba2-2.7b.ring-2k"
QWEN = "qwen1.5-4b.ring-short"

# 16 heads of 8, so that the reference builds its decay matrices in two
# blocks of heads; chunks of 16 over 64 tokens, so that the program's
# inter-chunk recurrence runs
TINY_MAMBA2 = {"d_model": 64, "vocab_size": 256, "num_layers": 2,
               "compute_dtype": "float32"}
TINY_SSM = {"d_state": 16, "head_dim": 8, "chunk_size": 16}


def _tiny_arch() -> dict:
    arch = load_cell(CELL).config["arch"]
    return dict(arch, **TINY_MAMBA2, ssm=dict(arch["ssm"], **TINY_SSM))


def _batch(tokens):
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)
    return {"tokens": tokens, "positions": pos}


def test_program_matches_the_reference_loss_and_gradients():
    """Both in float32 on the CPU, the same sums in another order (chunks
    against the whole sequence): the loss agrees to 1e-5 relative and each
    leaf's gradient to 1e-4 of its norm, a few hundred float32 roundings;
    a wrong term (a missing D x, an unscaled embedding, another norm eps)
    moves them by far more."""
    from repro.models import build_model

    arch = _tiny_arch()
    cfg = arch_config({"source": "tiny", "arch": arch})
    model = build_model(cfg)
    params = make_weights(purpose_key(2**33 + 3, "weights"),
                          jax.eval_shape(model.init, jax.random.key(0)))
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0,
                                arch["vocab_size"])
    ref = load_module(HERE / "references" / "mamba2.py")
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, arch, MATMULS["float32"])))(params)
    got, got_g = jax.jit(model.grad_fn())(params, _batch(tokens),
                                          jax.random.key(0))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for path, g, w in zip(leaf_paths(want_g), jax.tree.leaves(got_g),
                          jax.tree.leaves(want_g)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert np.linalg.norm(g - w) <= 1e-4 * np.linalg.norm(w), path


def _sequential(x, dt, a, b, c):
    """S_t = exp(dt_t A) S_{t-1} + dt_t B_t (x) x_t,  y_t = C_t . S_t, in a
    Python loop over t."""
    x, dt, b, c = (np.asarray(t, np.float64) for t in (x, dt, b, c))
    a = np.asarray(a, np.float64)
    bs, s, h, p = x.shape
    state = np.zeros((bs, h, p, b.shape[-1]))
    ys = []
    for t in range(s):
        state = (state * np.exp(dt[:, t] * a)[:, :, None, None]
                 + np.einsum("bhp,bn->bhpn", x[:, t] * dt[:, t, :, None],
                             b[:, t]))
        ys.append(np.einsum("bhpn,bn->bhp", state, c[:, t]))
    return np.stack(ys, 1), state


def _ssd_inputs(s, a, seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    bs, h, p, n = 1, len(a), 8, 8
    return (jax.random.normal(k[0], (bs, s, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (bs, s, h))),
            jnp.asarray(a, jnp.float32),
            jax.random.normal(k[2], (bs, s, n)),
            jax.random.normal(k[3], (bs, s, n)))


@pytest.mark.parametrize("chunk", [1, 8, 16, 64])
def test_ssd_chunked_matches_the_recurrence(chunk):
    """float32 against float64: 1e-5 of the output's scale."""
    from repro.models.ssm import ssd_chunked

    args = _ssd_inputs(64, [-0.5, -4.0])
    y, final = jax.jit(ssd_chunked, static_argnames="chunk")(*args,
                                                             chunk=chunk)
    want_y, want_final = _sequential(*args)
    scale = np.abs(want_y).max()
    assert np.abs(np.asarray(y) - want_y).max() <= 1e-5 * scale
    assert np.abs(np.asarray(final) - want_final).max() <= \
        1e-5 * np.abs(want_final).max()


def test_ssd_at_the_published_chunk_has_finite_gradients():
    """Chunks of 256 over 512 tokens with decays down to exp(-16 dt):
    above the diagonal cum_i - cum_j reaches hundreds, where exp
    overflows; the value and every gradient stay finite."""
    from repro.models.ssm import ssd_chunked

    args = _ssd_inputs(512, [-1.0, -16.0], seed=1)

    def f(*a):
        return jnp.sum(ssd_chunked(*a, chunk=256)[0] ** 2)

    value, grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4)))(
        *args)
    assert np.isfinite(float(value))
    for name, g in zip(("x", "dt", "a", "b", "c"), grads):
        assert np.all(np.isfinite(np.asarray(g))), name


def test_mamba2_loss_at_the_published_chunk_is_finite():
    """The model's loss and every gradient at chunk 256, 512 tokens."""
    from repro.models import build_model

    arch = dict(_tiny_arch(), ssm=dict(_tiny_arch()["ssm"], chunk_size=256))
    model = build_model(arch_config({"source": "tiny", "arch": arch}))
    params = make_weights(purpose_key(2**33 + 4, "weights"),
                          jax.eval_shape(model.init, jax.random.key(0)))
    tokens = jax.random.randint(jax.random.key(2), (1, 512), 0,
                                arch["vocab_size"])
    loss, grads = jax.jit(model.grad_fn())(params, _batch(tokens),
                                           jax.random.key(0))
    assert np.isfinite(float(loss))
    for path, g in zip(leaf_paths(grads), jax.tree.leaves(grads)):
        assert np.all(np.isfinite(np.asarray(g))), path


# the tiny Qwen round's per-step mean losses at seed 2**33 + 5, as read
# before the embedding flag existed
QWEN_TINY_LOSSES = [6.004410743713379, 6.135035037994385, 5.950666904449463,
                    5.926459789276123, 5.962622165679932, 6.097264766693115,
                    5.924834251403809, 5.844398498535156, 5.870052337646484,
                    5.87663459777832]


def _compiled_round(cell):
    """The cell's round compiled once: (its text, the checked round's
    per-step mean losses through that compiled round)."""
    b = harness._build(cell)
    s = harness._start(cell, b, 2**33 + 5)
    compiled = b["round_fn"].lower(s["state"], s["batches"][0],
                                   s["key"]).compile()
    _, losses, _, _ = harness.checked_round(dict(b, round_fn=compiled), s)
    return compiled.as_text(), losses, b["cfg"]


@pytest.fixture(scope="module")
def off_cache():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("repro.launch.compile_cache.enable_compile_cache",
                   lambda: "off")
        yield


@pytest.fixture(scope="module")
def qwen_round(off_cache):
    return _compiled_round(tiny_cell(QWEN))


@pytest.fixture(scope="module")
def ssm_round(off_cache):
    cell = tiny_cell(CELL)
    arch = dict(cell.config["arch"], d_model=64, vocab_size=256,
                ssm=dict(cell.config["arch"]["ssm"], **TINY_SSM))
    return _compiled_round(dataclasses.replace(
        cell, config=dict(cell.config, arch=arch)))


def test_default_embedding_scale_leaves_the_dense_round_alone(qwen_round):
    _, losses, cfg = qwen_round
    assert cfg.scale_embeddings
    assert losses.tolist() == pytest.approx(QWEN_TINY_LOSSES, rel=1e-5)


@pytest.mark.parametrize("scale", [True, False])
def test_embedding_scale_flag(scale):
    from repro.models import transformer

    cfg = dataclasses.replace(arch_config({"source": "tiny",
                                           "arch": _tiny_arch()}),
                              scale_embeddings=scale)
    table = jax.random.normal(jax.random.key(0), (256, 64))
    tokens = jnp.array([[3, 7, 255]])
    x = transformer._embed_inputs({"embed": {"table": table}}, cfg,
                                  {"tokens": tokens})
    want = table[tokens] * (8.0 if scale else 1.0)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(want))


def _reader():
    return load_module(HERE / "metrics" / "ssd_scan_pct.py")


def _op_names(text, names):
    return [re.search(r'op_name="([^"]+)"', line).group(1)
            for line in text.splitlines()
            if (m := re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line))
            and m.group(1) in names]


def test_ssd_scan_ops_lie_inside_the_grad_phase(ssm_round):
    """The scan's ops, forward and backward, are found and counted under
    ``grad``; the few in no phase are the mask and its broadcasts, which
    XLA computes once a round, outside every step."""
    from repro.models.ssm import SSD_SCOPE

    text, losses, _ = ssm_round
    assert np.isfinite(losses).all()
    assert _reader().SCOPE == SSD_SCOPE
    names = _reader().scan_ops(text)
    op_names = _op_names(text, names)
    assert any("transpose(" in o for o in op_names)       # backward
    assert any("transpose(" not in o for o in op_names)   # forward
    sc = scopes.hlo_scopes(text)
    in_grad = {n for n in names if sc[n] == "grad"}
    assert {sc[n] for n in names} <= {"grad", None}
    assert len(in_grad) > 0.9 * len(names)
    assert not any(scopes.PREFIX in o
                   for o in _op_names(text, names - in_grad))


def test_ssd_scan_pct_reads_the_scan_ops(ssm_round):
    text = ssm_round[0]
    reader = _reader()
    names = sorted(reader.scan_ops(text))
    ops = [Op(names[0], 10 * MS, 30 * MS, names[0]),
           Op(names[-1], 20 * MS, 40 * MS, names[-1]),   # overlaps
           Op("not-the-scan.1", 50 * MS, 90 * MS, "not-the-scan.1")]
    ctx = _ctx(trace=Trace({0: ops, 1: ops[:1]}, []), text=text)
    # device 0: 10-40 ms, device 1: 10-30 ms, of a 100 ms window
    assert reader.read(ctx) == pytest.approx(100 * (30 + 20) / 2 / 100)


def test_ssd_scan_pct_reads_nothing_in_a_dense_round(qwen_round):
    text = qwen_round[0]
    reader = _reader()
    assert reader.scan_ops(text) == set()
    assert reader.read(_ctx(text=text)) is None
    assert reader.read(_ctx()) is None


def _flops_per_token():
    return load_module(HERE / "references" / "mamba2.py").flops_per_token


def test_mamba2_flops_by_hand():
    arch = {"arch_type": "ssm", "num_layers": 2, "d_model": 8,
            "vocab_size": 32, "ssm": {"d_state": 4, "d_conv": 4,
                                      "expand": 2, "head_dim": 4,
                                      "chunk_size": 8}}
    proj = 2 * 8 * (2 * 16 + 2 * 4 + 4) + 2 * 16 * 8
    conv = 2 * 4 * (16 + 2 * 4)
    intra = 2 * 4 * 9 / 2 + 2 * 16 * 9 / 2   # (8 + 1) / 2 positions
    states = 2 * 2 * 16 * 4                  # chunk states, inter-chunk
    fwd = 2 * (proj + conv + intra + states) + 2 * 8 * 32
    assert _flops_per_token()(arch, 16) == 3 * fwd + 8
    # a sequence shorter than the chunk is one chunk
    intra4 = 2 * 4 * 5 / 2 + 2 * 16 * 5 / 2
    assert _flops_per_token()(arch, 4) == 3 * fwd + 8 - 3 * 2 * (
        intra - intra4)
    with pytest.raises(ValueError):
        _flops_per_token()(dict(arch, arch_type="dense"), 16)


def test_mamba2_cut_is_about_six_n():
    """The projections and the tied head dominate: ~6 x their
    parameters, plus the scan's few percent."""
    arch = load_cell(CELL).config["arch"]
    d, v = arch["d_model"], arch["vocab_size"]
    matmul_params = arch["num_layers"] * (d * (2 * 5120 + 2 * 128 + 80)
                                          + 5120 * d) + v * d
    per_token = _flops_per_token()(arch, 2048)
    assert 6 * matmul_params < per_token < 6.4 * matmul_params
