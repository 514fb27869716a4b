"""The timed path broken underneath, one fault at a time, and the control:
each must make ``correct`` come out false under the committed limits."""
import pytest

from chipbench import harness
from chipbench.tests import smoke

DRIVER = harness.load_module("drivers", "orch")


@pytest.mark.parametrize("fault", DRIVER.FAULTS)
def test_a_planted_fault_makes_the_run_incorrect(fault):
    driver, cfg_mod, out = smoke.drive(fault)
    limits = smoke.parts()[-1]
    ok, checks = harness.decide(driver.compare(out, cfg_mod, smoke.SEED),
                                limits)
    assert not ok, checks


def test_the_control_fails_a_number():
    """The reference in bfloat16, the precision below the configuration's
    float32, put in the program's place."""
    driver, cfg_mod, out = smoke.drive()
    limits = smoke.parts()[-1]
    numbers = dict(driver.control(out, cfg_mod, smoke.SEED), missing=0.0)
    ok, checks = harness.decide(numbers, limits)
    assert not ok, checks
