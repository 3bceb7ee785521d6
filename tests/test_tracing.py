"""Tracing: the ``feddec.*`` phase scopes of the compiled round, and the host
spans of ``train_loop`` on the profiler's clock.

Every engine runs ``engine.build_step_body``, whose phases are named
scopes; ``FlatSpec.flatten``/``unflatten`` and ``Model.grad_fn`` add their
own.  A scope reaches the ``op_name`` of each compiled instruction.  The
trainer's loop marks each fused round with a ``StepTraceAnnotation`` and
the token draw, the loss pull and the checkpoint with host spans.
"""

import contextlib
import glob
import io
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import FedConfig
from repro.core import FedDecConfig, init_state, make_feddec_round
from repro.core import flat as flat_lib
from repro.core import topology as topo
from repro.core.mixing import MixingDistribution
from repro.data import linreg
from repro.launch.train import tiny_lm_config, train_loop
from repro.models import build_model

PHASE = re.compile(r"feddec\.(\w+)")


def _phases(compiled_text: str) -> set:
    return {p for op_name in re.findall(r'op_name="([^"]*)"', compiled_text)
            for p in PHASE.findall(op_name)}


@pytest.mark.parametrize("layout", ["tree", "flat"])
def test_step_body_names_every_phase(layout):
    problem = linreg.make_problem(n=4, seed=0)
    md = MixingDistribution(topo.ring_graph(4, 1), p_fail=0.2,
                            scheme="metropolis")
    cfg = FedDecConfig(mixing=md, h=2, k=2, gossip_impl="dense")
    grad_fn = linreg.make_grad_fn(problem.m_rows)
    lr_fn = lambda t: jnp.asarray(0.01, jnp.float32)  # noqa: E731
    keys = jax.random.split(jax.random.key(1), 2)
    batches = jax.vmap(lambda k: linreg.sample_minibatch(problem, k, m=1))(
        keys)
    x0 = jnp.zeros(problem.d)
    if layout == "tree":
        state = init_state(x0, problem.n)
        round_fn = make_feddec_round(cfg, grad_fn, lr_fn, donate=False)
    else:
        spec = flat_lib.make_flat_spec(x0)
        state = flat_lib.init_flat_state(spec, x0, problem.n)
        round_fn = flat_lib.make_flat_feddec_round(cfg, spec, grad_fn, lr_fn,
                                                   donate=False)
    text = round_fn.lower(state, batches, jax.random.key(2)).compile()\
        .as_text()
    # a one-leaf spec's flatten and unflatten compile to no op; the LM's
    # are in perfbench/tests/test_perfbench_scopes.py
    assert _phases(text) >= {"sample_w", "update", "mix", "server"}
    assert "update_mix" not in _phases(text)


def test_model_grad_fn_is_the_grad_phase():
    cfg = tiny_lm_config(d_model=64, layers=1, vocab=64)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32),
             "positions": jnp.arange(8)[None]}
    text = jax.jit(model.grad_fn()).lower(params, batch, None).compile()\
        .as_text()
    assert _phases(text) == {"grad"}


@pytest.fixture(scope="module")
def traced_loop(tmp_path_factory):
    """Three fused rounds of a tiny LM under ``jax.profiler.trace``."""
    from jax.profiler import ProfileData

    log_dir = str(tmp_path_factory.mktemp("trace"))
    cfg = tiny_lm_config(d_model=64, layers=1, vocab=64)
    fed = FedConfig(n_agents=4, h=2, k=2, graph="ring1",
                    gossip_impl="dense")
    out = io.StringIO()
    with contextlib.redirect_stdout(out), jax.profiler.trace(log_dir):
        train_loop(cfg, fed, steps=6, per_agent_batch=1, seq_len=8,
                   log_every=2)
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("feddec.")]
    return spans, out.getvalue()


def test_train_loop_spans_one_round_each(traced_loop):
    spans, _ = traced_loop
    rounds = sorted((s for s in spans if s[0] == "feddec.round"),
                    key=lambda s: s[1])
    assert [r[3].get("step_num") for r in rounds] == [0, 1, 2]
    for name in ("feddec.sample", "feddec.loss_pull"):
        inner = [s for s in spans if s[0] == name]
        assert len(inner) == 3, name
        # each inside its own round
        assert sorted(next(i for i, r in enumerate(rounds)
                           if r[1] <= s[1] and s[2] <= r[2])
                      for s in inner) == [0, 1, 2], name


def test_train_loop_rate_leaves_out_the_compiling_round(traced_loop):
    _, log = traced_loop
    lines = [ln for ln in log.splitlines() if ln.startswith("[train] step")]
    assert len(lines) == 3
    assert "first round, compiled" in lines[0]
    assert all(re.search(r"\(\d+\.\d+ steps/s\)", ln) for ln in lines[1:])
