"""The last line's schema, the checks printed beside it, and the peaks."""
import json

import pytest

from chipbench import harness


def test_result_line_schema_and_checks_last():
    res = harness.Result(attempted=10, failed=0,
                         metrics={"serve_tok_s": 1.5, "setup_s": 2.0},
                         device={"platform": "tpu", "kind": "TPU v5 lite",
                                 "count": 1, "memory_peak_bytes": 5})
    res.checks = [harness.Check("gap", 0.1, 0.2)]
    line = harness.result_line(res, {"serve_tok_s": "tokens/s",
                                     "setup_s": "s"})
    out = json.loads(line)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True
    assert out["metrics"]["serve_tok_s"] == {"value": 1.5,
                                             "unit": "tokens/s"}
    assert out["checks"] == {"gap": {"value": 0.1, "limit": 0.2}}


def test_breakdown_comes_before_checks_and_failures_are_incorrect():
    res = harness.Result(attempted=4, failed=1, breakdown={
        "device_ops": [["fusion", 0.5]], "idle_gaps": []})
    res.checks = [harness.Check("gap", 0.1, 0.2)]
    out = json.loads(harness.result_line(res, {}))
    assert list(out)[-2:] == ["breakdown", "checks"]
    assert out["correct"] is False


@pytest.mark.parametrize("value,limit,ok", [
    (0.1, 0.2, True), (0.3, 0.2, False), (float("nan"), 0.2, False),
    (float("inf"), 0.2, False), (0.1, None, False)])
def test_check(value, limit, ok):
    assert harness.Check("x", value, limit).ok is ok


def test_peaks_known_and_unknown_device():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")
