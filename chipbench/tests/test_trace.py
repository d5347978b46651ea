"""The trace reduction on a small trace recorded on a TPU v5e: one jitted
step (a Pallas matmul and a fusion) run three times, the host sleeping
between runs inside a ``host_wait`` annotation."""
from chipbench import trace
from chipbench.spec import ROOT

TRACE = ROOT / "chipbench" / "testdata" / "trace_small.xplane.pb"


def test_programs_ops_and_busy_time():
    s = trace.reduce(TRACE)
    assert len(s.devices) == 1
    secs, runs = s.module("jit_small_step")
    assert runs == 3 and 5e-5 < secs < 1e-4
    kernel, calls = s.op("tpu_custom_call", module="jit_small_step")
    assert calls == 3 and 0 < kernel < secs
    assert s.op("tpu_custom_call", module="jit_other") == (0.0, 0.0)
    assert kernel < s.busy_s <= secs


def test_breakdown_names_ops_and_the_host_during_gaps():
    b = trace.reduce(TRACE).breakdown()
    assert b["device_ops"][0][0] == "tpu_custom_call:small_step"
    assert [n for n, _ in b["device_ops"]] == [
        "tpu_custom_call:small_step", "fusion:tanh_reduce_fusion"]
    names = [n for n, _ in b["idle_gaps"]]
    assert names and set(names) <= {"time sleep", "host_wait"}
    assert 0.004 < sum(t for _, t in b["idle_gaps"]) < 0.01


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
