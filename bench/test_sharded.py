"""The four-chip cell on four CPU devices: the sharded wave loop passes the
check against the reference run per chip stream, and the loop with the
exchange between chips left out fails it."""

from bench.testing import tiny_run


def test_sharded_program_is_correct(tmp_path):
    result, err = tiny_run(tmp_path, devices=4, workload="siard3.deep.chips4",
                           seed=2**31 + 29, seconds=1)
    assert result["correct"], err[-2000:]
    assert result["device"]["count"] == 4
    assert result["window_compiles"] == 0 and result["failed"] == 0


def test_no_exchange_is_caught(tmp_path):
    result, err = tiny_run(tmp_path, devices=4, workload="siard3.deep.chips4",
                           seed=13, seconds=1, fault="no_exchange")
    assert not result["correct"], err[-2000:]
