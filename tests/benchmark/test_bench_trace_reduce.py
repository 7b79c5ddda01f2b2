"""trace_reduce.py on a hand-built trace whose answers are known."""

import os

import pytest

from benchmarks import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "small_trace.textproto")) as f:
        return T.reduce_trace(T.load_text_proto(f.read()))


def test_window_and_busy_share(reduced):
    # ops cover [0,100) and [150,200) us of a window [0,210) us
    assert reduced.devices == 1
    assert reduced.window == (0.0, 210000.0)
    assert reduced.busy_s == pytest.approx(150e-6)
    assert reduced.idle_pct() == pytest.approx(100 * 60 / 210)


def test_self_time_subtracts_nested_children(reduced):
    # while.1 lasts 100 us but its body covers 80 of them
    ops = dict(reduced.top_ops(10))
    assert ops["while.1"] == pytest.approx(20e-6)
    assert ops["fusion.1"] == pytest.approx(80e-6)  # 30 nested + 50 alone
    assert ops["closed_call.3 bf16[4,16] tpu_custom_call"] == pytest.approx(30e-6)
    assert reduced.top_ops(1)[0][0] == "fusion.1"
    assert sum(ops.values()) == pytest.approx(reduced.busy_s)


def test_exposed_collective_share(reduced):
    # the async all-gather is in flight for 30 us; fusion.1 hides 10 of
    # them, all-gather.2 runs the other 20 with nothing else on the device
    assert reduced.collective[0] == [(30000.0, 60000.0)]
    assert reduced.exposed_collective_pct() == pytest.approx(100 * 20 / 210)
    assert reduced.op_seconds(r" tpu_custom_call$") == pytest.approx(30e-6)


def test_hlo_event_names_become_short_labels():
    name = ('%fusion.7 = bf16[8192,4096]{1,0:T(8,128)(2,1)} fusion(bf16[8192,'
            '1024]{1,0:T(8,128)(2,1)} %a, f32[4]{0} %b), kind=kOutput')
    assert T.op_label(name) == "fusion.7 bf16[8192,4096] fusion"
    assert T.op_label("%while.10 = (s32[]{:T(128)}, bf16[4,8]{1,0}) "
                      "while((s32[]{:T(128)}, bf16[4,8]{1,0}) %t)") == \
        "while.10 s32[] while"
    assert T.op_label("fusion.1") == "fusion.1"
    assert T.COLLECTIVE.match(T.op_label(
        "%all-gather-start.7 = (f32[8]{0}, f32[32]{0}) all-gather-start("
        "f32[8]{0} %p), dimensions={0}"))


def test_gaps_go_to_the_host_span_that_covers_most(reduced):
    gaps = reduced.idle_gaps(longest=5, sums=5)
    assert gaps[0] == ["bench/submit", pytest.approx(50e-6)]
    assert gaps[1] == ["bench/engine.step", pytest.approx(10e-6)]
    assert ["sum bench/submit", pytest.approx(50e-6)] in gaps


def test_host_time_outside_the_device(reduced):
    # step spans of 120 and 50 us hold 100 and 40 us of device work
    assert reduced.host_outside_device_s("bench/engine.step") == [
        pytest.approx(20e-6), pytest.approx(10e-6)]


def test_interval_helpers():
    assert T.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 4), (6, 9)], []) == [(0, 4), (6, 9)]
    assert T.total([(0, 3), (5, 7)]) == 5
    assert T.COLLECTIVE.match("all-gather-start.12")
    assert T.COLLECTIVE.match("reduce-scatter.3")
    assert not T.COLLECTIVE.match("fusion.all-gather")


def test_a_recorded_trace_loads_and_keeps_the_bench_spans(tmp_path):
    """The real loader on a trace recorded here (CPU: no device plane, so no
    device number comes out of it)."""
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench/engine.step"):
                jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    r = T.reduce_trace(T.load(str(tmp_path)))
    assert len(r.spans["bench/engine.step"]) == 2
    assert r.devices == 0 and r.idle_pct() is None and r.busy_s == 0.0
