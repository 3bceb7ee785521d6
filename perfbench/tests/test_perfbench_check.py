"""The check that decides ``correct``, on tiny versions of the cells.

The reference model agrees with the program's model where both compute
in float32; a sound run is correct; the control (the reference with
float8 products) and each planted fault are not.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import algorithm1, compare, control, faults, harness
from perfbench.spec import HERE, arch_config, load_module
from perfbench.tests.tiny import tiny_cell
from perfbench.weights import make_weights

CELL = "qwen1.5-4b.ring-short"


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "off")


def test_reference_model_matches_program_model_in_float32():
    from repro.models import build_model

    cell = tiny_cell(CELL)
    arch = dict(cell.config["arch"], compute_dtype="float32")
    model = build_model(arch_config(dict(cell.config, arch=arch)))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = make_weights(jax.random.key(3), shapes)
    tokens = jax.random.randint(jax.random.key(4), (2, 32), 0, 256)
    batch = {"tokens": tokens,
             "positions": jnp.broadcast_to(jnp.arange(32), (2, 32))}
    ref = load_module(HERE / "references" / "dense_transformer.py").loss
    with jax.default_matmul_precision("highest"):
        lp, gp = model.grad_fn()(params, batch, None)
    lr, gr = jax.value_and_grad(
        lambda p: ref(p, tokens, arch, algorithm1.MATMULS["float32"]))(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=2e-3,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_ring_metropolis():
    w = algorithm1.ring_metropolis(4)
    np.testing.assert_allclose(w, w.T)
    np.testing.assert_allclose(w.sum(1), 1.0)
    np.testing.assert_allclose([w[0, 1], w[0, 3], w[0, 0]], 1 / 3)
    assert w[0, 2] == 0


def test_mix_faults_change_w_as_stated():
    w = algorithm1.ring_metropolis(4)
    np.testing.assert_array_equal(faults.mixing_matrix("no_mix", w),
                                  np.eye(4))
    bad = faults.mixing_matrix("one_edge", w)
    assert bad[0, 1] == 0 and bad[0, 0] == pytest.approx(2 / 3)
    np.testing.assert_allclose(bad.sum(1), 1.0)
    assert not np.allclose(bad, bad.T)
    assert faults.mixing_matrix(None, w) is w
    assert (w[0] == algorithm1.ring_metropolis(4)[0]).all()


def test_numbers_against_the_reference_itself():
    rng = np.random.default_rng(0)
    ref = algorithm1.ReferenceRound(
        losses=rng.uniform(5, 6, 10), change=rng.uniform(0, 1, (4, 7)),
        spread=rng.uniform(0, 1, (4, 7)), grad1=np.r_[1e-9, np.ones(6)])
    same = compare.numbers(ref.losses, ref.change, ref.spread, ref)
    assert same == {"loss_gap": 0.0, "change_gap": 0.0, "spread_gap": 0.0,
                    "segments_left_out": 1}
    nan = ref.spread.copy()
    nan[2, 3] = np.nan
    assert compare.numbers(ref.losses, ref.change, nan, ref)[
        "spread_gap"] == float("inf")
    ok, checks = compare.judge(same, {"loss_gap": 0.0, "spread_gap": 0.1})
    assert ok and set(checks) == {"loss_gap", "spread_gap"}


def test_sound_run_is_correct_and_faults_are_not():
    cell = tiny_cell(CELL)
    sound = harness.run(cell, seed=2**33 + 9, seconds=0.0, trace=False,
                        t_start=time.time())
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] == 1 and sound["failed"] == 0
    assert list(sound) == ["correct", "attempted", "failed", "metrics",
                           "device", "checks"]
    assert set(sound["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                     "setup_s"}
    assert set(sound["checks"]) == {"loss_gap", "change_gap", "spread_gap"}
    for fault in ("frozen", "half_batch"):
        bad = harness.run(cell, seed=2**33 + 9, seconds=0.0, trace=False,
                          t_start=time.time(), fault=fault)
        assert not bad["correct"], (fault, bad["checks"])


@pytest.mark.parametrize("fault", ["no_mix", "one_edge"])
def test_mix_faults_in_the_program_are_not_correct(fault):
    cell = tiny_cell(CELL)
    bad = harness.run(cell, seed=2**33 + 9, seconds=0.0, trace=False,
                      t_start=time.time(), fault=fault)
    assert not bad["correct"], bad["checks"]
    assert bad["checks"]["spread_gap"]["value"] > \
        bad["checks"]["spread_gap"]["limit"]


def test_control_fails_the_limits():
    cell = tiny_cell(CELL)
    out = control.readings(cell, [5, 2**31 + 3],
                           fault_names=("half_batch", "no_mix", "one_edge"),
                           emit=lambda line: None)
    for nums in out["program"]:
        assert compare.judge(nums, cell.limits)[0], nums
    for kind in ("control", "half_batch", "no_mix", "one_edge"):
        for nums in out[kind]:
            assert not compare.judge(nums, cell.limits)[0], (kind, nums)
