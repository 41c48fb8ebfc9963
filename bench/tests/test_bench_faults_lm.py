"""The comparison that decides ``correct`` fails for each planted fault
and for the control, in the ``qwen05b.p2-fedavg`` cell (see ``faults.py``)."""
import pytest

from bench.tests.faults import (fresh_programs,  # noqa: F401
                                check_control, check_fault, check_sound)

NAME = "qwen05b.p2-fedavg"


def test_sound_run_is_correct(fresh_programs, monkeypatch):  # noqa: F811
    check_sound(NAME, monkeypatch)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "doubled",
                                   "stale_key"])
def test_planted_fault_is_not_correct(fault, fresh_programs,  # noqa: F811
                                      monkeypatch):
    check_fault(NAME, fault, monkeypatch)


def test_control_is_not_correct(fresh_programs):  # noqa: F811
    check_control(NAME)
