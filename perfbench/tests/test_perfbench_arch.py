"""Any architecture the program registers goes through the benchmark's
files: its configuration as JSON, a rule for each of its weights, and a
round through the harness.

The tiny rounds below are the harness's plumbing at a size a test can
hold, with the program's own smoke sizes (a chunk of 16 for the SSD): they
do not reach the SSD backward's overflow at the published chunk of 256,
which is the program's to fix before a Mamba2 cell can run.
"""

import dataclasses
import hashlib
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.seeds import purpose_key
from perfbench.spec import arch_config
from perfbench.tests.tiny import tiny_cell
from perfbench.weights import RG_LRU_C, leaf_paths, make_weights

CELL = "qwen1.5-4b.ring-short"

# std of N(0, 1) truncated at ±2
TRUNCATED_STD = 0.8796256610342398


def _registered():
    from repro.configs import ARCH_NAMES

    return ARCH_NAMES


def _cut(cfg):
    """Two periods of the config's layer pattern after its leading
    layers."""
    from repro.models.transformer import plan_layers

    plan = plan_layers(cfg, cfg.num_layers)
    return dataclasses.replace(cfg, num_layers=plan.prefix + 2 * plan.period)


def _as_json(cfg) -> dict:
    """A configuration file's ``source`` and ``arch`` for ``cfg``, through
    JSON."""
    arch = dataclasses.asdict(cfg)
    for key in ("param_dtype", "compute_dtype"):
        arch[key] = jnp.dtype(arch[key]).name
    return json.loads(json.dumps({"source": arch.pop("source"),
                                  "arch": arch}))


def _shapes(cfg):
    from repro.models import build_model

    return jax.eval_shape(build_model(cfg).init, jax.random.key(0))


def _drawn(cfg, seed=11) -> dict:
    """{leaf path: values} of the benchmark's weights for ``cfg``."""
    w = make_weights(jax.random.key(seed), _shapes(cfg))
    return dict(zip(leaf_paths(w), map(np.asarray, jax.tree.leaves(w))))


def _leaves(drawn: dict, suffix: str) -> list:
    found = [v for p, v in drawn.items() if p.endswith(suffix)]
    assert found, suffix
    return found


@pytest.mark.parametrize("name", _registered())
def test_registered_config_round_trips_through_json(name):
    from repro.configs import get_config

    cfg = _cut(get_config(name))
    back = arch_config(_as_json(cfg))
    assert back == cfg
    assert type(back.block_pattern) is tuple
    for key in ("moe", "mla", "ssm"):
        assert type(getattr(back, key)) is type(getattr(cfg, key))


@pytest.mark.parametrize("name", _registered())
def test_make_weights_has_a_rule_for_every_leaf(name):
    from repro.configs import get_config

    shapes = _shapes(_cut(get_config(name)))
    out = jax.eval_shape(lambda k: make_weights(k, shapes),
                         jax.random.key(1))
    assert jax.tree.structure(out) == jax.tree.structure(shapes)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(shapes)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_stacked_experts_and_mla_up_projections_fan_in():
    """std = 1/sqrt(d_in): experts (E, d_in, d_out) by d_in, MLA's
    (rank, heads, head_dim) up-projections by the rank."""
    from repro.configs import get_config

    cfg = get_config("deepseek-v3-671b").smoke()
    drawn = _drawn(cfg)
    d, f = cfg.d_model, cfg.moe.d_ff_expert
    rank, q_rank = cfg.mla.kv_lora_rank, cfg.mla.q_lora_rank
    for suffix, d_in in (("/moe/wi/w", d), ("/moe/wg/w", d),
                         ("/moe/wo/w", f), ("/attn/wk_b/w", rank),
                         ("/attn/wv_b/w", rank), ("/attn/wq_b/w", q_rank)):
        for x in _leaves(drawn, suffix):
            assert x.std() * math.sqrt(d_in) == pytest.approx(
                TRUNCATED_STD, rel=0.05), suffix


def test_mamba2_and_rg_lru_draws_lie_in_published_ranges():
    from repro.configs import get_config

    cfg = get_config("mamba2-2.7b").smoke()
    drawn = _drawn(cfg)
    for a_log in _leaves(drawn, "/a_log"):
        assert (a_log >= 0).all() and (a_log <= math.log(16) + 1e-6).all()
        assert a_log.min() < math.log(4) and a_log.max() > math.log(8)
    for dt_bias in _leaves(drawn, "/dt_bias"):
        dt = np.asarray(jax.nn.softplus(dt_bias))
        assert (dt >= 1e-3 * (1 - 1e-4)).all()
        assert (dt <= 1e-1 * (1 + 1e-4)).all()
        assert dt.min() < 1e-2 < dt.max()
    for d_skip in _leaves(drawn, "/d_skip"):
        assert np.abs(d_skip - 1).max() < 0.15 and d_skip.std() > 0
    bound = 1 / math.sqrt(cfg.ssm.d_conv)
    for x in _leaves(drawn, "/conv_w") + _leaves(drawn, "/conv_b"):
        assert np.abs(x).max() <= bound and np.abs(x).max() > 0.8 * bound

    rg = _drawn(get_config("recurrentgemma-9b").smoke())
    for lam in _leaves(rg, "/lam"):
        decay = np.exp(-RG_LRU_C * np.asarray(jax.nn.softplus(lam)))
        assert (decay >= 0.9 - 1e-6).all() and (decay <= 0.999 + 1e-6).all()
        assert decay.min() < 0.92 and decay.max() > 0.99


def test_tiny_qwen_weights_match_the_pinned_checksum():
    """The rules for leaves that existed before the nested configs keep
    their fold of the key and their draw, bit for bit."""
    cfg = arch_config(tiny_cell(CELL).config)
    w = make_weights(purpose_key(2**33 + 9, "weights"), _shapes(cfg))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(w):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == ("8e9c8b56ccf125e9e2f0205b03d79210"
                             "9201e936239938607e8ca24763bdc312")


TINY_SSM = {
    "name": "tiny-ssm", "arch_type": "ssm", "num_layers": 2,
    "d_model": 64, "num_heads": 1, "num_kv_heads": 1, "d_ff": 0,
    "vocab_size": 256, "attention_kind": "none", "rope_kind": "none",
    "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 32,
            "chunk_size": 16},
    "param_dtype": "float32", "compute_dtype": "bfloat16"}

TINY_MOE_MLA = {
    "name": "tiny-moe-mla", "arch_type": "moe", "num_layers": 3,
    "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "d_ff": 128,
    "vocab_size": 256, "attention_kind": "mla",
    "mla": {"kv_lora_rank": 32, "q_lora_rank": 16, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16},
    "moe": {"num_experts": 4, "num_shared": 1, "top_k": 2,
            "d_ff_expert": 32, "first_dense_layers": 1, "d_ff_dense": 128},
    "mlp_kind": "swiglu", "param_dtype": "float32",
    "compute_dtype": "bfloat16"}


@pytest.mark.parametrize("arch", [TINY_SSM, TINY_MOE_MLA],
                         ids=["ssm", "moe_mla"])
def test_tiny_round_of_another_architecture(arch, monkeypatch):
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "off")
    cell = dataclasses.replace(tiny_cell(CELL), config={
        "source": "a tiny configuration written in the test", "arch": arch})
    b = harness._build(cell)
    assert b["cfg"].arch_type == arch["arch_type"]
    s = harness._start(cell, b, 2**33 + 5)
    state, losses, change, spread = harness.checked_round(b, s)
    assert losses.shape[0] == cell.traffic["h"]
    assert np.isfinite(losses).all(), losses
    assert np.isfinite(change).all() and np.isfinite(spread).all()
    assert (change > 0).any()
