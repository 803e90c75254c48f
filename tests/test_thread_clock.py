"""tools/thread_clock: the calibration that says whether a host's
thread CPU clock is fit to be read (ISSUE 37).  Here only that it runs
and judges as it says: the verdict on THIS host is not asserted (under
xdist a spinner is preempted)."""

import json

import pytest

from ompi_tpu.tools import thread_clock


@pytest.mark.parametrize("threads,spin_us,sleep_us", [
    (1, 500, 0), (1, 0, 2000), (3, 300, 1500)])
def test_cycle_times_its_own_spins(threads, spin_us, sleep_us):
    row = thread_clock.cycle(threads, spin_us, sleep_us, 0.12)
    period_us = threads * spin_us + sleep_us
    assert row["iters"] == int(0.12e6 / period_us)
    # every turn spins at least what it was told
    assert row["spun_ms"] >= row["iters"] * threads * spin_us / 1e3
    assert row["clock_ms"] >= 0
    assert (row["ratio"] is None) == (spin_us == 0)
    # the sleeps are taken: the run lasts about its periods
    assert row["wall_s"] >= 0.9 * row["iters"] * period_us / 1e6


@pytest.mark.parametrize("row,verdict", [
    # the chip tool's host (my chip run, PR 37): sleeps read as work,
    # work in step with the tick reads as nothing
    ({"ratio": None, "clock_ms": 1440.0, "wall_s": 2.12, "threads": 1},
     False),
    ({"ratio": 0.0, "clock_ms": 0.0, "wall_s": 2.1, "threads": 1}, False),
    ({"ratio": 3.788, "clock_ms": 3720.0, "wall_s": 3.0, "threads": 8},
     False),
    # Linux
    ({"ratio": None, "clock_ms": 4.83, "wall_s": 0.5, "threads": 1}, True),
    ({"ratio": 1.062, "clock_ms": 537.7, "wall_s": 0.55, "threads": 8},
     True)])
def test_fair_judges_a_reading(row, verdict):
    assert thread_clock.fair(row) is verdict


def test_main_prints_a_line_a_cycle_and_the_verdict(capsys):
    rc = thread_clock.main(["--seconds", "0.05", "a:1:200:0", "b:2:100:500"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln.get("cycle") for ln in lines] == ["a", "b", None]
    last = lines[-1]
    assert last["fit"] is (rc == 0) and last["fit"] is (not last["unfit"])
    assert last["read_ns"] > 0
