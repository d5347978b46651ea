"""The traffic generator: the same seed gives the same traffic, and every
seed gets the same work."""
import numpy as np
import pytest

from chipbench import spec, traffic


def _mix(name="hymba-serve-chat"):
    return spec.find_cell(name).mix


def test_schedule_repeats_for_a_seed():
    mix = _mix()
    a = traffic.serve_schedule(mix, 2**33 + 7, 32001, 30)
    b = traffic.serve_schedule(mix, 2**33 + 7, 32001, 30)
    assert len(a) == len(b) > mix["initial"]
    for (t, p, n), (u, q, m) in zip(a, b):
        assert t == u and n == m and np.array_equal(p, q)


def test_every_seed_sends_the_same_lengths_at_the_same_times():
    mix = _mix()
    one = traffic.serve_schedule(mix, 1, 32001, 30)
    two = traffic.serve_schedule(mix, 2, 32001, 30)
    assert [(t, len(p), n) for t, p, n in one] == \
        [(t, len(p), n) for t, p, n in two]
    assert any(not np.array_equal(p, q) for (_, p, _), (_, q, _) in zip(one, two))
    outs = [n for _, _, n in one]
    assert len({len(p) for _, p, _ in one}) > 1 and sorted(outs) != outs


@pytest.mark.parametrize("seconds", [0.3, 10, 30, 51])
def test_due_times_fill_the_window_at_the_rate(seconds):
    mix = _mix()
    due = traffic.due_times(mix, seconds)
    gap = 1 / mix["rate_per_s"]
    assert due[:mix["initial"]] == [0.0] * mix["initial"]
    rest = due[mix["initial"]:]
    assert all(0 < t < seconds for t in rest)
    assert np.allclose(np.diff([0.0] + rest), gap)
    assert rest == [] or rest[-1] + gap >= seconds - 1e-9


def test_prompt_lengths_stay_on_the_warmed_grid():
    mix = _mix()
    prompts, outputs = traffic.request_lengths(mix)
    assert len(prompts) == len(outputs) == mix["lengths"]
    assert set(prompts) <= set(mix["prompt"]["grid"])
    assert set(traffic.prompt_grid(mix)) == set(prompts)
    assert min(outputs) >= mix["output"]["min"]
    assert max(outputs) <= mix["output"]["max"]
    assert max(prompts) + max(outputs) <= mix["max_seq"]


def test_lognormal_quantiles_hold_the_median():
    q = traffic.lognormal_quantiles(101, 256, 0.7, 64, 1024)
    assert q[50] == 256 and q == sorted(q)
    assert min(q) >= 64 and max(q) <= 1024
