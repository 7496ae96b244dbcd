"""The faults a federation cell can have, planted under a run of the
harness at toy widths on the CPU: each must make the run not correct."""
import time

import jax
import pytest

from bench import federation

SEED = 2**31 + 77


@pytest.mark.parametrize("fault", ["stale", "half_batch"])
def test_fed_run_with_a_planted_fault_is_not_correct(tiny_fed, fault):
    w, conf, job, limits = tiny_fed
    # limits between the program's toy readings (under 0.02) and the
    # faults' (0.1 and more at this size)
    limits = dict(limits, grad_gap=0.05, change_gap=0.05)
    res, checks = federation.run(w, conf, job, limits, SEED, 1.0, 0,
                                 jax.devices()[:1], time.perf_counter(),
                                 faults=(fault,))
    assert not res["correct"], checks
