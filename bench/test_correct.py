"""The check that decides `correct`: the program passes it, and the
reference computed in bfloat16, put in the program's place, fails it."""

import pytest

from bench.testing import tiny_run


@pytest.mark.parametrize("workload", ["siard3.deep"])
def test_program_is_correct(workload, tmp_path):
    result, err = tiny_run(tmp_path, workload=workload, seed=2**31 + 17,
                           seconds=1)
    assert result["correct"], err[-2000:]
    assert result["window_compiles"] == 0
    assert result["attempted"] >= 1 and result["failed"] == 0


@pytest.mark.parametrize("workload", ["siard3.deep"])
def test_bfloat16_control_is_not_correct(workload, tmp_path):
    result, err = tiny_run(tmp_path, workload=workload, seed=5, seconds=1,
                           control="bfloat16")
    assert not result["correct"], err[-2000:]
    failed = [k for k, v in result["checks"].items()
              if v["value"] > v["limit"]]
    assert failed
