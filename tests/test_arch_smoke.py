"""Per-architecture smoke tests (deliverable f).

For every assigned architecture: instantiate the REDUCED same-family variant
(≤2–3 layers, d_model ≤ 512, ≤4 experts — ``ArchConfig.smoke()``), then on
CPU:

  * one forward pass — assert logits shape and finiteness;
  * one FedDec train step over 4 agents — assert params update, stay finite;
  * one decode step with caches — assert shape/finiteness (decoder archs);
  * prefill↔decode agreement on a short sequence (exact for the non-MoE
    archs; MoE uses a high capacity factor to avoid legitimate token drops).

The FULL production configs are exercised only via launch/dryrun.py
(ShapeDtypeStruct, no allocation), as specified.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.core import FedDecConfig, init_state, make_feddec_step
from repro.core import topology as topo
from repro.core.mixing import MixingDistribution
from repro.launch.specs import concrete_batch
from repro.models import build_model

N_AGENTS = 4


@pytest.fixture(scope="module", params=ARCH_NAMES)
def arch(request):
    cfg = get_config(request.param).smoke()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return request.param, cfg, model, params


def _batch(cfg, batch=2, seq=16, agents=None, key=None):
    return concrete_batch(cfg, agents, batch, seq,
                          key or jax.random.key(1), enc_len=8)


class TestForward:
    def test_logits_shape_and_finite(self, arch):
        name, cfg, model, params = arch
        b = _batch(cfg)
        logits, aux = jax.jit(lambda p, x: model.logits(p, x))(params, b)
        assert logits.shape == (2, 16, cfg.vocab_size), name
        assert np.isfinite(np.asarray(logits, np.float32)).all(), name
        assert np.isfinite(float(aux))

    def test_loss_finite_and_reasonable(self, arch):
        name, cfg, model, params = arch
        loss = float(jax.jit(model.loss)(params, _batch(cfg)))
        assert np.isfinite(loss), name
        assert 0.0 < loss < 50.0, (name, loss)


class TestFedTrainStep:
    def test_one_feddec_step(self, arch):
        """One full Algorithm-1 step over 4 agents on CPU."""
        name, cfg, model, params = arch
        g = topo.ring_graph(N_AGENTS, k=1)
        fcfg = FedDecConfig(mixing=MixingDistribution(g, scheme="metropolis"),
                            h=2, k=2)
        step = make_feddec_step(fcfg, model.grad_fn(),
                                lambda t: jnp.asarray(1e-3), donate=False)
        state = init_state(params, N_AGENTS)
        batch = _batch(cfg, agents=N_AGENTS)
        new_state, metrics = step(state, batch, jax.random.key(2))
        assert int(new_state.step) == 2
        assert np.isfinite(float(metrics["loss"])), name
        moved = finite = 0
        for old, new in zip(jax.tree.leaves(state.params),
                            jax.tree.leaves(new_state.params)):
            finite += int(np.isfinite(np.asarray(new, np.float32)).all())
            moved += int(not np.allclose(np.asarray(old, np.float32),
                                         np.asarray(new, np.float32)))
        leaves = len(jax.tree.leaves(state.params))
        assert finite == leaves, name
        assert moved > leaves // 2, (name, moved, leaves)  # params updated


class TestDecode:
    def test_decode_step_shapes(self, arch):
        name, cfg, model, params = arch
        b, cache_len = 2, 16
        caches = model.init_caches(b, cache_len, dtype=jnp.float32)
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = model.encode(params, _batch(cfg))
        db = concrete_batch(cfg, None, b, 1, jax.random.key(3), decode=True,
                            enc_len=8)
        db.pop("enc_out", None)
        logits, new_caches = jax.jit(
            lambda p, x, c: model.decode_step(p, x, c, enc_out=enc_out)
        )(params, db, caches)
        assert logits.shape == (b, 1, cfg.vocab_size), name
        assert np.isfinite(np.asarray(logits, np.float32)).all(), name
        assert jax.tree.structure(new_caches) == jax.tree.structure(caches)

    def test_prefill_decode_agreement(self, arch):
        """Token-by-token decode reproduces the prefill logits."""
        name, cfg, model, params = arch
        if cfg.moe is not None:
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
            model = build_model(cfg)
            params = model.init(jax.random.key(0))
        b, s = 2, 12
        batch = _batch(cfg, batch=b, seq=s)
        if cfg.frontend == "vision":
            # decode path is text-only; drop the patch prefix for this check
            batch.pop("frontend_embeds", None)
            cfg = dataclasses.replace(cfg, frontend=None)
            model = build_model(cfg)
        from repro.models import transformer
        enc_out = None
        full, _, _, enc_out = transformer.forward(params, batch, cfg)
        caches = model.init_caches(b, s, dtype=jnp.float32)
        outs = []
        step = jax.jit(lambda p, x, c: model.decode_step(p, x, c,
                                                         enc_out=enc_out))
        for t in range(s):
            db = {"tokens": batch["tokens"][:, t:t + 1],
                  "positions": batch["positions"][:, t:t + 1]}
            if "mrope_positions" in batch:
                db["mrope_positions"] = batch["mrope_positions"][:, :, t:t + 1]
            lg, caches = step(params, db, caches)
            outs.append(lg[:, 0])
        dec = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(np.asarray(dec, np.float32),
                                   np.asarray(full, np.float32),
                                   atol=2e-3, rtol=2e-3, err_msg=name)


class TestConfigIntegrity:
    @pytest.mark.parametrize("name", ARCH_NAMES)
    def test_exact_assigned_dims(self, name):
        """The full configs carry the exact assignment-table dimensions."""
        cfg = get_config(name)
        expected = {
            "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
            "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
            "deepseek-v3-671b": (61, 7168, 128, 128, 18432, 129280),
            "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
            "mamba2-2.7b": (64, 2560, 1, 1, 0, 50288),
            "deepseek-v2-lite-16b": (27, 2048, 16, 16, 10944, 102400),
            "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
            "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
            "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
            "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
        }[name]
        got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
               cfg.d_ff, cfg.vocab_size)
        assert got == expected, (name, got, expected)

    @pytest.mark.parametrize("name", ARCH_NAMES)
    def test_smoke_reduction_bounds(self, name):
        sm = get_config(name).smoke()
        assert sm.num_layers <= 3
        assert sm.d_model <= 512
        if sm.moe is not None:
            assert sm.moe.num_experts <= 4

    def test_moe_details(self):
        v3 = get_config("deepseek-v3-671b")
        assert (v3.moe.num_experts, v3.moe.num_shared, v3.moe.top_k) == \
            (256, 1, 8)
        assert v3.moe.d_ff_expert == 2048
        assert v3.mla.kv_lora_rank == 512
        lite = get_config("deepseek-v2-lite-16b")
        assert (lite.moe.num_experts, lite.moe.top_k) == (64, 6)
        assert lite.mla.kv_lora_rank == 512 and lite.mla.q_lora_rank == 0

    def test_ssm_details(self):
        m = get_config("mamba2-2.7b")
        assert m.ssm.d_state == 128
        assert m.ssm.num_heads(m.d_model) == 80

    def test_patterns(self):
        rg = get_config("recurrentgemma-9b")
        assert rg.block_pattern == ("rglru", "rglru", "attn")
        g3 = get_config("gemma3-12b")
        locals_ = [g3.is_local_layer(i) for i in range(12)]
        assert locals_ == [True] * 5 + [False] + [True] * 5 + [False]
