"""The trace reduction and the metric readers on a small synthetic trace."""

import pytest

from perfbench import trace as tl
from perfbench.spec import HERE, load_module
from perfbench.trace import Op, Span, Trace

MS = 1_000_000


def _trace():
    # window: dispatch at 0 ms, loss pull ending at 100 ms
    spans = [Span("bench.pool_cycle", 0, 1 * MS),
             Span("bench.dispatch", 1 * MS, 5 * MS),
             Span("bench.loss_pull", 5 * MS, 100 * MS),
             Span("other.thing", 60 * MS, 90 * MS)]
    dev0 = [Op("fusion.1", 10 * MS, 30 * MS, "fusion.1"),
            Op("custom-call.7", 30 * MS, 40 * MS, "custom-call.7"),
            Op("fusion.2", 35 * MS, 50 * MS, "fusion.2"),          # overlaps
            Op("all-reduce.3", 50 * MS, 60 * MS, "all-reduce.3"),
            Op("custom-call.7", 70 * MS, 80 * MS, "custom-call.7"),
            Op("fusion.9", 95 * MS, 130 * MS, "fusion.9")]          # clipped
    dev1 = [Op("collective-permute-done", 10 * MS, 20 * MS,
               "collective-permute-done.2"),
            Op("fusion.1", 15 * MS, 18 * MS, "fusion.1")]
    return Trace({0: dev0, 1: dev1}, spans)


def test_union_and_busy():
    assert tl.union_ns([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    t = _trace()
    lo, hi = tl.window_of(t.spans)
    assert (lo, hi) == (1 * MS, 100 * MS)
    # 10-60 and 70-80 and 95-100
    assert tl.busy_ns(t.devices[0], lo, hi) == 65 * MS


def test_kernel_time_and_calls():
    t = _trace()
    ns, calls = tl.kernel_ns(t.devices[0], {"custom-call.7"}, 0, 100 * MS)
    assert (ns, calls) == (20 * MS, 2)


def test_top_ops_and_idle_gaps():
    t = _trace()
    lo, hi = tl.window_of(t.spans)
    top = tl.top_ops(t.devices[0], lo, hi, n=2)
    assert top == [["custom-call.7", 0.02], ["fusion.1", 0.02]] or \
        top == [["fusion.1", 0.02], ["custom-call.7", 0.02]]
    gaps = tl.idle_gaps(t.devices[0], t.spans, lo, hi)
    # idle 1-10 (dispatch 1-5 and loss pull 5-10), 60-70, 80-95
    assert gaps[0] == ["bench.loss_pull", pytest.approx(0.015)]
    assert [g[1] for g in gaps] == pytest.approx([0.015, 0.01, 0.009])
    assert gaps[1][0] in ("other.thing", "bench.loss_pull")


def _ctx(**kw):
    t = _trace()
    ctx = {"trace": t, "lo": 0, "hi": 100 * MS, "device_ids": [0, 1],
           "chips": 2, "steps": 10, "flops_per_step": 1e12,
           "peaks": {"bf16_flops": 200e12, "hbm_bytes_per_s": 800e9},
           "kernels": ["custom-call.7"], "update_mix_bytes": 1e8,
           "compile_s": 12.5}
    ctx.update(kw)
    return ctx


def _read(name, ctx):
    return load_module(HERE / "metrics" / f"{name}.py").read(ctx)


def test_metric_readers():
    ctx = _ctx()
    # busy over [0, 100): dev0 10-60, 70-80, 95-100 = 65; dev1 10-20 = 10
    assert _read("device_idle_pct", ctx) == pytest.approx(
        100 * (1 - 37.5 / 100))
    assert _read("mfu_pct", ctx) == pytest.approx(
        100 * 10 * 1e12 / (0.1 * 2 * 200e12))
    assert _read("update_mix_roofline", ctx) == pytest.approx(
        100 * 1e8 * 2 / (0.02 * 800e9))
    assert _read("compile_s", ctx) == 12.5


def test_end_to_end_readers():
    ctx = {"tokens": 40960, "window_s": 2.0,
           "memory_peak_bytes": 3 * 2 ** 30, "setup_s": 31.5}
    assert _read("tokens_per_s", ctx) == 20480
    assert _read("peak_hbm_gib", ctx) == 3.0
    assert _read("setup_s", ctx) == 31.5


def test_readers_find_nothing():
    assert _read("update_mix_roofline",
                 _ctx(update_mix_bytes=None)) is None
    assert _read("update_mix_roofline", _ctx(kernels=["nope.1"])) is None
    assert _read("device_idle_pct", _ctx(device_ids=[])) is None


def test_hlo_names_and_innermost_ops():
    assert tl.hlo_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.3"
    assert tl.hlo_name("%update_mix_pallas.12 = (f32[4,9]) custom-call()") \
        == "update_mix_pallas.12"
    assert tl.hlo_name("plain") == "plain"
    ops = [Op("w", 0, 100, "while"), Op("a", 0, 10, "a"),
           Op("b", 20, 30, "b"), Op("c", 120, 130, "c"),
           Op("d", 125, 140, "d")]
    assert [o.hlo_op for o in tl.innermost(ops)] == ["a", "b", "c", "d"]
