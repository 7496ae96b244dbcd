"""A federation cell's path through the harness at toy widths on the CPU:
the rounds, the comparison with the reference round, its control, and the
planted faults a training cell can have."""
import time

import jax
from bench import federation

SEED = 2**31 + 77


def _run(cell, faults=(), prec_ctl=None):
    w, conf, job, limits = cell
    return federation.run(w, conf, job, limits, SEED, 1.0, 0,
                          jax.devices()[:1], time.perf_counter(),
                          faults=faults, prec_ctl=prec_ctl)


def test_fed_run_is_correct_and_control_and_half_batch_read_far_off(
        tiny_fed):
    res, checks = _run(tiny_fed)
    got = {n: v for n, v, _ in checks}
    assert res["correct"], checks
    assert got["rows_distinct"] == 1 and got["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"round_s", "setup_s"}
    # the control put in the program's place is not correct; it and the
    # half-batch fault read at least three times what the program reads,
    # on one of the two numbers
    w, conf, job, limits = tiny_fed
    limits = dict(limits, grad_gap=0.05, change_gap=0.05)
    res, checks = _run((w, conf, job, limits), prec_ctl="fp8")
    ctl = {n: v for n, v, _ in checks}
    assert not res["correct"], checks
    assert ctl["program_grad_gap"] == got["grad_gap"]
    for tag in ("", "half_batch_"):
        assert max(ctl[f"{tag}grad_gap"] / got["grad_gap"],
                   ctl[f"{tag}change_gap"] / got["change_gap"]) > 3, ctl
