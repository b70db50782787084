"""Trace reduction: busy union, module time, kernel match, idle gaps."""
import types

import pytest

from bench import trace_reduce as tr


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def synthetic():
    host = plane("/host:CPU", python=[
        ev("bench_window", 1000, 10000), ev("plan_step", 4000, 2500),
        ev("np.asarray", 8100, 800)])
    dev = plane("/device:TPU:0",
                XLA_Ops=[ev("fusion.1", 500, 1500),      # clipped to 1000
                         ev("fusion.2", 1800, 1000),     # overlaps fusion.1
                         ev("%paged_prefill_attention.9 = bf16[4,8,768,128]"
                            " custom-call(s32[4,514] %gte.3)", 7000, 1000),
                         ev("fusion.3", 10500, 1000)],   # clipped at 11000
                XLA_Modules=[ev("jit_model_step(12)", 1200, 2000),
                             ev("jit_model_step(12)", 6800, 1500),
                             ev("jit_other", 9000, 500)])
    return [host, dev]


def test_busy_is_the_union_of_op_intervals_in_the_window():
    red = tr.reduce_planes(synthetic())
    # [1000, 2800] + [7000, 8000] + [10500, 11000]
    assert red["busy_s"] == pytest.approx((1800 + 1000 + 500) / 1e9)
    assert red["window_s"] == pytest.approx(10000 / 1e9)
    assert red["chips"] == 1


def test_module_time_and_kernel_match():
    red = tr.reduce_planes(synthetic())
    assert tr.module_times(red, r"model_step") == \
        pytest.approx([2000 / 1e9, 1500 / 1e9])
    from bench import spec
    pattern = spec.metric_reader("paged_attn_roofline.prefill").__globals__[
        "PATTERN"]
    assert tr.op_seconds(red, pattern) == pytest.approx(1000 / 1e9)


def test_idle_gaps_go_to_the_innermost_host_event():
    red = tr.reduce_planes(synthetic())
    gaps = dict(red["idle_gaps"])
    # [2800, 7000] mid 4900: plan_step; [8000, 10500] mid 9250: none
    # inner (np.asarray ends 8900), so bench_window is excluded
    assert gaps["plan_step"] == pytest.approx(4200 / 1e9)
    assert gaps["(no host event)"] == pytest.approx(2500 / 1e9)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tr.reduce_planes([plane("/device:TPU:0", XLA_Ops=[])])


def test_union_and_gaps_agree():
    iv = [(0, 5), (3, 8), (10, 12), (11, 11)]
    assert tr.union_ns(iv, 0, 20) == 10
    assert sum(e - s for s, e in tr.gaps_ns(iv, 0, 20)) == 10


def test_recorded_trace_loads_and_finds_its_window():
    """A real profiler file (three ``plan_step`` spans and three calls of a
    jitted function inside ``bench_window``, recorded on the CPU, which
    has no device plane): the window is found and no device time is
    invented."""
    from bench.tests.conftest import FIXTURES
    red = tr.reduce_planes(tr.load(FIXTURES).planes)
    assert 0.006 < red["window_s"] < 0.1
    assert red["chips"] == 0 and red["busy_s"] == 0.0
    assert red["ops"] == [] and red["modules"] == []
