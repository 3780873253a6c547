"""Faults planted in the program's timed path make `correct` false."""

import pytest

from bench.testing import tiny_run


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_fault_is_caught(fault, tmp_path):
    result, err = tiny_run(tmp_path, workload="siard3.deep", seed=11,
                           seconds=1, fault=fault)
    assert not result["correct"], err[-2000:]
