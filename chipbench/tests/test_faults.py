"""A whole run with the timed path broken underneath comes out not
correct, for each fault a solve cell can have (``drive.py``)."""

import pytest

from chipbench.tests.test_harness import drive


@pytest.mark.parametrize("fault,devices", [
    ("unchanged", 1),   # the solve hands back its initial state
    ("half", 1),        # half of the answer left out
    ("answer", 1),      # one entry altered where it is produced
    ("exchange", 4),    # the halo exchange between chips left out
])
def test_fault_is_caught(fault, devices):
    r = drive("--fault", fault, "--devices", str(devices))
    assert r["correct"] is False
    assert r["failed"] >= 1
    assert r["checks"]["true_relres_max"]["value"] > r["checks"]["true_relres_max"]["limit"]


def test_sound_four_device_run_is_correct():
    r = drive("--devices", "4")
    assert r["correct"] is True
