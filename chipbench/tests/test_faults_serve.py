"""A serving run whose timed path is broken underneath reports
``correct: false``, and so does the control put in the program's place.
The harness's look for a chip is skipped; the rest of a run is driven as
the benchmark drives it, at tiny sizes on the CPU."""
import pytest

from chipbench import faults, spec
from chipbench.runners import serve_lm
from chipbench.tests.tiny import HYMBA, write_tree


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = write_tree(tmp_path_factory.mktemp("tree"), limit=1e-3)
    return spec.find_cell("hymba-serve-chat", root)


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_decode_is_not_correct(cell, fault):
    res = serve_lm.run(cell, seed=2**33 + 3, seconds=0.3, trace=False,
                       require_chip=False, fault=fault)
    assert res.attempted > cell.mix["initial"] and res.failed == 0
    assert res.checks and not res.correct
    assert all(c.value > 1e-3 for c in res.checks)


def test_unknown_fault_is_an_error():
    with pytest.raises(ValueError):
        with faults.plant("no_such_fault"):
            pass


@pytest.fixture(scope="module")
def bf16_cell(tmp_path_factory):
    """A small model in the cell's own type, under the cell's own limit,
    compared over a dozen requests (a few hundred served tokens)."""
    root = write_tree(tmp_path_factory.mktemp("bf16"), requests=12,
                      model=dict(HYMBA, dtype="bfloat16", n_layers=4,
                                 d_model=128, d_ff=256))
    return spec.find_cell("hymba-serve-chat", root)


def test_control_in_the_programs_place_is_not_correct(bf16_cell):
    res = serve_lm.run(bf16_cell, seed=2**31 + 11, seconds=0.6, trace=False,
                       require_chip=False, controls=("fp8",))
    control = res.controls["fp8"]
    limits = bf16_cell.mix["check"]["limits"]
    assert {c.name: c.limit for c in control.checks} == limits
    assert res.notes["sampled_requests"] == 12
    assert not control.correct


def test_a_request_the_engine_refuses_ends_the_run(cell, monkeypatch):
    import numpy as np

    from chipbench import traffic

    too_long = [(0.0, np.zeros(cell.mix["max_seq"] + 1, np.int32), 2)]
    monkeypatch.setattr(traffic, "serve_schedule", lambda *a: too_long)
    with pytest.raises(ValueError, match="max_seq"):
        serve_lm.run(cell, seed=7, seconds=0.2, trace=False,
                     require_chip=False)
