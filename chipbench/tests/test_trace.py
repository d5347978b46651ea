"""The trace reduction: on a small trace recorded on a TPU v5e (one jitted
step, a Pallas matmul and a fusion, run three times, the host sleeping
between runs inside a ``host_wait`` annotation), given the slice it is
read over; and on made-up profiles of the same shape, for what a slice
does to busy time, gaps and the window."""
import time
from types import SimpleNamespace as NS

import pytest

from chipbench import trace
from chipbench.spec import ROOT

TRACE = ROOT / "chipbench" / "testdata" / "trace_small.xplane.pb"
# on the trace's host clock, 50 us before the first program starts and
# after the last one ends (the device's clock runs 1,321,986 ns behind)
SMALL_SLICE = (45_814_191, 52_393_916)


def _event(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def _with_slice(planes, start, end):
    """A profile of these planes and one more host plane whose thread
    holds the slice's annotation."""
    tracer = NS(name="tracer", events=[_event(trace.SLICE, start, end)])
    return NS(planes=list(planes) + [NS(name="/host:tracer", lines=[tracer])])


def _small():
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(TRACE))
    return trace.reduce(_with_slice(prof.planes, *SMALL_SLICE), chips=[0])


MS = 1_000_000
OFF = 7 * MS        # host clock minus device clock in the made-up profiles


def _made_up(programs, *, chips=(0,), slice_ms=(20, 80), host=(),
             launches=True):
    """A profile in which each chip ran one operation per program over
    ``programs`` ((start, end) in ms on the device's clock), each launched
    on the host just before it starts; ``host`` adds Python events (name,
    start, end in ms on the host clock)."""
    devices = []
    for i in chips:
        mods = [_event("jit_step(1)", s * MS, e * MS) for s, e in programs]
        ops = [_event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)",
                      s * MS, e * MS) for s, e in programs]
        devices.append(NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Modules", events=mods),
            NS(name="XLA Ops", events=ops)]))
    main = [_event("PJRT_LoadedExecutable_Execute", s * MS + OFF - MS // 10,
                   s * MS + OFF) for s, _ in programs] if launches else []
    python = [_event(n, s * MS, e * MS) for n, s, e in host]
    cpu = NS(name="/host:CPU", lines=[NS(name="main", events=main),
                                      NS(name="python", events=python)])
    lo, hi = (t * MS + OFF for t in slice_ms)
    return _with_slice(devices + [cpu], lo, hi)


def test_programs_ops_and_busy_time():
    s = _small()
    assert len(s.devices) == 1
    secs, runs = s.module("jit_small_step")
    assert runs == 3 and 5e-5 < secs < 1e-4
    kernel, calls = s.op("tpu_custom_call", module="jit_small_step")
    assert calls == 3 and 0 < kernel < secs
    assert s.op("tpu_custom_call", module="jit_other") == (0.0, 0.0)
    assert kernel < s.busy_s <= secs
    assert s.window_s == (SMALL_SLICE[1] - SMALL_SLICE[0]) / 1e9


def test_breakdown_names_ops_and_the_host_during_gaps():
    b = _small().breakdown()
    assert b["device_ops"][0][0] == "tpu_custom_call:small_step"
    assert [n for n, _ in b["device_ops"]] == [
        "tpu_custom_call:small_step", "fusion:tanh_reduce_fusion"]
    names = [n for n, _ in b["idle_gaps"]]
    assert names and set(names) <= {"time sleep", "host_wait"}
    assert 0.004 < sum(t for _, t in b["idle_gaps"]) < 0.01


def test_saturated_chip_reads_busy_equal_to_window():
    # programs back to back from 10 ms before the slice to 20 ms after it
    s = trace.reduce(_made_up([(t, t + 10) for t in range(10, 100, 10)]),
                     chips=[0])
    assert s.window_s == s.busy_s == 0.06
    assert s.gaps == []


def test_programs_outside_the_slice_count_nothing():
    s = trace.reduce(_made_up([(0, 15), (40, 45), (85, 99)]), chips=[0])
    assert s.busy_s == 0.005 and s.window_s == 0.06
    # the module and operation sums stay over the whole trace
    assert s.module("jit_step") == (0.034, 3)
    assert sum(t for _, t in s.gaps) == 55 * MS


def test_a_gap_inside_the_slice_counts_once_named_by_the_host():
    prof = _made_up([(0, 30), (50, 60), (62, 100), (110, 120)],
                    host=[("loop", 0 + 7, 130 + 7),
                          ("host_wait", 29 + 7, 51 + 7),
                          ("time sleep", 61 + 7, 62 + 7)])
    s = trace.reduce(prof, chips=[0])
    # the slice is 20-80 ms: the gap at 100-110 ms lies outside it
    assert s.busy_s == 0.038 and s.window_s == 0.06
    assert s.breakdown()["idle_gaps"] == [["host_wait", 0.02],
                                          ["time sleep", 0.002]]
    s = trace.reduce(prof, chips=[0], min_gap_ns=5 * MS)
    assert s.gaps == [("host_wait", 20 * MS)]


def test_busy_time_averages_over_the_cells_chips_only():
    prof = _made_up([(30, 40)], chips=(0, 1))
    prof.planes.append(NS(name="/device:TPU:2", lines=[]))   # an idle chip
    assert trace.reduce(prof, chips=[0]).busy_s == 0.01
    assert trace.reduce(prof, chips=[0, 1]).busy_s == 0.01
    with pytest.raises(ValueError, match="no plane for"):
        trace.reduce(prof, chips=[0, 3])


@pytest.mark.parametrize("prof,match", [
    (NS(planes=_made_up([(30, 40)]).planes[:-1]), "annotations, not one"),
    (_made_up([(30, 40)], launches=False), "no program launch"),
    (_made_up([(0, 10), (90, 95)]), "no operation ran"),
], ids=["no-slice", "no-launch", "idle-slice"])
def test_a_trace_that_cannot_be_read_over_its_slice_raises(prof, match):
    with pytest.raises(ValueError, match=match):
        trace.reduce(prof, chips=[0])


def test_tracer_marks_its_slice_in_the_trace():
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from chipbench import harness

    step = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    tracer = harness.Tracer(True, "test-slice")
    tracer.start_in(0.0, 0.05)
    deadline = time.perf_counter() + 60
    while tracer.t1 == 0.0 and time.perf_counter() < deadline:
        step(x).block_until_ready()
    tracer.join()
    try:
        planes = list(ProfileData.from_file(str(tracer.xplane())).planes)
    finally:
        tracer.cleanup()
    start, end = trace._slice(planes)
    # the annotation holds both perf_counter readings, and little more
    assert 0 <= (end - start) / 1e9 - (tracer.t1 - tracer.t0) < 0.005


def test_op_key_reads_hlo_text():
    assert trace.op_key("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %a), "
                        "kind=kLoop") == ("fusion", "fusion")
    assert trace.op_key("%all-reduce.3 = f32[4]{0} all-reduce(f32[4]{0} "
                        "%x), replica_groups={}") == ("all-reduce",
                                                       "all-reduce")
    assert trace.op_key('%k.1 = f32[4]{0} custom-call(f32[4]{0} %x), '
                        'custom_call_target="tpu_custom_call"') == (
        "tpu_custom_call:k", "custom-call")


def test_interval_arithmetic():
    assert trace._union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace._length(trace._union([(0, 2), (1, 4), (6, 7)])) == 5
    assert trace._clip([(0, 3), (5, 7), (9, 12)], 2, 10) == [
        (2, 3), (5, 7), (9, 10)]
    assert trace._idle([(2, 3), (5, 7)], 1, 10) == [(1, 2), (3, 5), (7, 10)]
    assert trace._idle([(1, 10)], 1, 10) == []
