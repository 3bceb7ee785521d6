"""Device time by phase: the scope map of compiled text, its join with the
device trace, and the phases the tiny cell's rounds compile to."""

import dataclasses
import re

import pytest

from perfbench import harness, phases, scopes
from perfbench.tests.test_perfbench_trace import MS, _ctx, _read, _trace
from perfbench.tests.tiny import tiny_cell

CELL = "qwen1.5-4b.ring-short"

SNIPPET = """\
HloModule jit_round_fn, entry_computation_layout={(f32[4,8]{1,0})->f32[4,8]}

%fused_computation.3 (param_0.1: f32[4,8]) -> f32[4,8] {
  %param_0.1 = f32[4,8]{1,0} parameter(0)
  ROOT %negate.2 = f32[4,8]{1,0} negate(%param_0.1), metadata={op_name="jit(round_fn)/while/body/closed_call/feddec.update_mix/vmap(feddec.grad)/neg"}
}

%region_0.body (arg_tuple.1: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %arg_tuple.1 = (s32[], f32[4,8]{1,0}) parameter(0)
  %gte.1 = f32[4,8]{1,0} get-tuple-element(%arg_tuple.1), index=1
  %dot.5 = f32[4,8]{1,0} dot(%gte.1, %gte.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %fusion.3 = f32[4,8]{1,0} fusion(%dot.5), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(round_fn)/while/body/closed_call/feddec.update_mix/vmap(feddec.grad)/neg"}
  %update_mix.1 = f32[4,8]{1,0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_fn)/while/body/closed_call/feddec.update_mix/update_mix/pallas_call"}
  %slice.4 = f32[4,4]{1,0} slice(%update_mix.1), slice={[0:4], [0:4]}, metadata={op_name="jit(round_fn)/while/body/closed_call/feddec.update_mix/feddec.unflatten/slice"}
  ROOT %tuple.2 = (s32[], f32[4,8]{1,0}) tuple(%gte.1, %update_mix.1)
}

ENTRY %main.9 (Arg_0.1: f32[4,8]) -> (f32[4,8], f32[4]) {
  %Arg_0.1 = f32[4,8]{1,0} parameter(0), metadata={op_name="x"}
  %copy.486 = f32[4,8]{1,0} copy(%Arg_0.1)
  %while.1 = (s32[], f32[4,8]{1,0}) while(%copy.486), condition=%region_1.cond, body=%region_0.body, metadata={op_name="jit(round_fn)/while/body/closed_call/feddec.grad/while"}
  %reduce.7 = f32[4]{0} reduce(%Arg_0.1), to_apply=%add, metadata={op_name="jit(round_fn)/while/body/closed_call/feddec.server/cond/branch_1_fun/feddec.server/reduce_sum"}
  %copy.9 = f32[4]{0} copy(%reduce.7)
  %copy.10 = f32[4,8]{1,0} copy(%Arg_0.1)
  %add.8 = f32[4,8]{1,0} add(%copy.10, %copy.10), metadata={op_name="jit(round_fn)/add"}
  %fusion.12 = f32[4,8]{1,0} fusion(%copy.10), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(round_fn)/feddec.grad/neg"}
  ROOT %tuple.11 = (f32[4,8]{1,0}, f32[4]{0}) tuple(%add.8, %copy.9)
}
"""


def test_hlo_scopes_on_a_snippet():
    got = scopes.hlo_scopes(SNIPPET)
    assert got["fusion.3"] == "grad"            # last feddec.* component
    assert got["negate.2"] == "grad"
    assert got["update_mix.1"] == "update_mix"  # the kernel call itself
    assert got["slice.4"] == "unflatten"
    assert got["reduce.7"] == "server"
    assert got["add.8"] is None                 # op_name outside any phase
    assert got["Arg_0.1"] is None
    assert "HloModule" not in got and "main.9" not in got
    # no op_name: the phase of the instruction holding its computation,
    assert got["dot.5"] == "grad"               # in the loop feddec.grad runs
    assert got["gte.1"] == "grad"
    # else the one phase of the nearest named instructions using it,
    assert got["copy.486"] == "grad"            # feeds the loop
    # else of those it reads,
    assert got["copy.9"] == "server"            # only the ROOT uses it
    # and none where they disagree
    assert got["copy.10"] is None               # add.8 (none), fusion.12
    assert got["tuple.11"] is None              # add.8 (none), server


def _scoped():
    return {"fusion.1": "grad", "custom-call.7": "update_mix",
            "fusion.2": "flatten", "all-reduce.3": None,
            "fusion.9": "unflatten"}


def test_phase_ns_on_the_synthetic_trace():
    dev0, sc = _trace().devices[0], _scoped()
    lo, hi = 1 * MS, 100 * MS
    assert scopes.phase_ns(dev0, sc, ("grad",), lo, hi) == 20 * MS
    # custom-call.7 at 30-40 and 70-80, clipped to the window
    assert scopes.phase_ns(dev0, sc, ("update_mix",), lo, hi) == 20 * MS
    # flatten 35-50 overlaps update_mix 30-40: the union counts 30-50 once
    assert scopes.phase_ns(dev0, sc, ("update_mix", "flatten"), 0,
                           60 * MS) == 20 * MS
    assert scopes.phase_ns(dev0, sc, ("unflatten",), lo, hi) == 5 * MS
    assert scopes.phase_ns(dev0, sc, ("mix",), lo, hi) == 0
    assert scopes.phase_ns(dev0, {}, ("grad",), lo, hi) == 0


def test_phase_table_on_the_synthetic_trace():
    t = scopes.phase_table(_trace().devices[0], _scoped(), 1 * MS, 100 * MS)
    assert t["phases_s"]["grad"] == pytest.approx(0.02)
    assert t["phases_s"]["flatten"] == pytest.approx(0.015)
    assert set(t["phases_s"]) == set(scopes.PHASES)
    assert t["busy_s"] == pytest.approx(0.065)
    assert t["no_phase_s"] == pytest.approx(0.01)
    assert t["no_phase_ops"] == [["all-reduce.3", pytest.approx(0.01)]]


def test_grad_flops_pct_and_flat_copy_pct_on_the_synthetic_trace():
    ctx = _ctx(scopes=_scoped())
    # grad: dev0 fusion.1 10-30, dev1 fusion.1 15-18: 23 ms over both chips
    assert scopes.grad_flops_pct(ctx) == pytest.approx(
        100 * 10 * 1e12 / (0.023 * 200e12))
    # flatten 35-50 + unflatten 95-100 on dev0, none on dev1, of 100 ms
    assert scopes.flat_copy_pct(ctx) == pytest.approx(100 * 20 / 2 / 100)


def test_phase_readings_where_nothing_ran():
    none_scoped = {k: None for k in _scoped()}
    assert scopes.grad_flops_pct(_ctx(scopes=none_scoped)) is None
    assert scopes.flat_copy_pct(_ctx(scopes=none_scoped)) == 0.0
    assert scopes.grad_flops_pct(_ctx()) is None      # no scope map
    assert scopes.flat_copy_pct(_ctx()) is None
    assert scopes.grad_flops_pct(_ctx(scopes=_scoped(),
                                      device_ids=[])) is None


def test_phase_report_plumbing():
    text = SNIPPET.replace("update_mix.1", "custom-call.7").replace(
        "fusion.3", "fusion.1")
    ctx = _ctx(scopes=scopes.hlo_scopes(text), device_ids=[0])
    out = phases.phase_report(ctx)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["phases_s"]["update_mix"] == pytest.approx(0.02)
    assert out["flat_copy_pct"] == 0.0
    # one chip: fusion.1 at 10-30 ms on device 0
    assert out["grad_flops_pct"] == pytest.approx(
        100 * 10 * 1e12 / (0.02 * 200e12))


def test_phase_metric_readers():
    """The readers of the two per-layer metrics are scopes.py's own."""
    ctx = _ctx(scopes=_scoped())
    assert _read("grad_flops_pct", ctx) == scopes.grad_flops_pct(ctx)
    assert _read("flat_copy_pct", ctx) == scopes.flat_copy_pct(ctx)
    assert _read("grad_flops_pct", ctx) > 0 < _read("flat_copy_pct", ctx)
    assert _read("grad_flops_pct", _ctx()) is None


@pytest.fixture(scope="module")
def tiny_traced():
    """The tiny cell's fused round, traced once through ``harness.run``.
    The CPU has no published peaks; any will do for the plumbing."""
    import time

    cell = tiny_cell(CELL)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("repro.launch.compile_cache.enable_compile_cache",
                   lambda: "off")
        mp.setattr(harness, "peaks_for",
                   lambda kind: {"bf16_flops": 1e12,
                                 "hbm_bytes_per_s": 1e11})
        return phases.traced_run(cell, seed=2**33 + 7, seconds=0.0,
                                 t_start=time.time())


def _ops(text, kinds):
    return re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S+\s+"
                      rf"(?:{kinds})\(", text, re.M)


def test_traced_run_keeps_text_and_trace(tiny_traced):
    result, ctx = tiny_traced
    assert result["correct"], result["checks"]
    assert "feddec." in ctx["text"]
    assert ctx["scopes"] == scopes.hlo_scopes(ctx["text"])
    assert {s.name for s in ctx["trace"].spans} >= {"bench.dispatch",
                                                    "bench.loss_pull"}
    cell = tiny_cell(CELL)
    tr, arch = cell.traffic, cell.config["arch"]
    assert ctx["flops_per_step"] == harness.reference_model(
        cell).flops_per_token(arch, tr["seq_len"]) * tr["agents"] \
        * tr["per_agent_batch"] * tr["seq_len"]
    # the CPU's trace has no device plane: the phase readers find nothing
    assert "mfu_pct" in result["metrics"]
    assert set(result["metrics"]) <= {m["name"] for m in cell.per_layer}


def test_tiny_fused_round_phases(tiny_traced):
    text = tiny_traced[1]["text"]
    sc = scopes.hlo_scopes(text)
    assert set(sc.values()) >= {"unflatten", "grad", "flatten",
                                "update_mix", "server"}
    assert not set(sc.values()) & {"update", "mix"}
    dots = _ops(text, "dot|convolution")
    assert dots
    assert all(sc[d] is not None for d in dots), [
        d for d in dots if sc[d] is None]
    assert all(sc[d] == "grad" for d in dots
               if sc[d] not in ("update_mix", "mix", "server"))


def test_unfused_dense_round_phases():
    """The two-op round with link failures: W is drawn every step."""
    cell = tiny_cell(CELL)
    cell = dataclasses.replace(cell, traffic=dict(
        cell.traffic, gossip_impl="dense", fuse_update_mix=False))

    def failing_links(fcfg):
        return dataclasses.replace(fcfg, mixing=dataclasses.replace(
            fcfg.mixing, p_fail=0.2))

    b = harness._build(cell, (lambda g: g, lambda f: f, failing_links))
    s = harness._start(cell, b, 2**33 + 7)
    sc = scopes.hlo_scopes(b["round_fn"].lower(
        s["state"], s["batches"][0], s["key"]).compile().as_text())
    assert set(sc.values()) >= {"sample_w", "update", "mix", "unflatten",
                                "grad", "flatten", "server"}
    assert "update_mix" not in set(sc.values())
