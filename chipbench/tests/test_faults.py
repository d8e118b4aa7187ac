"""The comparison that decides ``correct`` fails where it must.

Runs the tiny CPU cell (``tiny.py``) through the whole harness past its
look for a chip: once as it is, which must come out correct; once with
the lower-precision control read, which must fail a limit; and once
with each fault of ``faults.py`` planted under the timed path, which
must come out not correct. A few minutes on a CPU:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_faults.py
"""
from __future__ import annotations

import pytest

import tiny


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return tiny.make_copy(tmp_path_factory.mktemp("bench"))


def _failed(result):
    return [k for k, c in result["checks"].items()
            if c["value"] is None or c["value"] > c["limit"]]


def test_sound_run_is_correct(copy):
    result, _out, err = tiny.run(copy)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert "limit" in err.strip().splitlines()[-1]


def test_control_is_not_correct(copy):
    result, _out, _err = tiny.run(copy, "--control", "1")
    assert result["correct"] is False, result["checks"]
    assert _failed(result), result["checks"]


@pytest.mark.parametrize("fault, number", [
    ("step_unchanged", "train_grad_gap"),
    ("params_unchanged", "train_change_gap"),
    ("update_flipped", "train_loss_gap"),
    ("half_batch", "train_grad_gap"),
    ("score_altered", "score_prob_gap"),
    ("answer_altered", "answers_wrong"),
])
def test_fault_is_not_correct(copy, fault, number):
    result, _out, _err = tiny.run(copy, fault=fault)
    assert result["correct"] is False
    assert number in _failed(result), result["checks"]
