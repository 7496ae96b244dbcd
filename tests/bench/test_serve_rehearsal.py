"""A serving cell's path through the harness at toy widths on the CPU: the
open loop, the metrics, the comparison with the reference, its control,
and a planted fault."""
import time

import jax
import pytest

from bench import serving

SEED = 2**31 + 77


def _run(cell, faults=(), prec_ctl=None):
    w, conf, traffic, limits = cell
    return serving.run(w, conf, traffic, limits, SEED, 2.0, 0,
                       jax.devices()[:1], time.perf_counter(), faults=faults,
                       prec_ctl=prec_ctl)


def _cell(tiny_serve, **lim):
    w, conf, traffic, limits = tiny_serve
    return w, conf, traffic, dict(limits, checked_tokens=40, **lim)


def test_serving_run_is_correct_and_its_control_is_not(tiny_serve):
    # at toy widths the program serves the reference's argmax (gap 0) and
    # float8 puts another token first by about 0.17: a limit between them
    cell = _cell(tiny_serve, logit_gap=0.05)
    res, checks = _run(cell)
    got = {n: v for n, v, _ in checks}
    assert res["correct"], checks
    assert got["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert res["attempted"] > 10 and res["failed"] == 0
    assert res["device"]["count"] == 1
    # the control put in the program's place is not correct
    res, checks = _run(cell, prec_ctl="fp8")
    ctl = {n: v for n, v, _ in checks}
    assert not res["correct"], checks
    assert ctl["logit_gap"] > 0.05 >= ctl["program_logit_gap"]


def test_serving_run_with_an_altered_token_is_not_correct(tiny_serve):
    res, checks = _run(_cell(tiny_serve), faults=("token",))
    assert not res["correct"], checks


@pytest.mark.parametrize("lo,hi,want", [
    (32, 736, [64, 128, 256, 512, 768]), (512, 896, [512, 768, 1024]),
    (4, 16, [64])])
def test_warm_up_reaches_exactly_the_buckets_of_the_mix(lo, hi, want):
    got = serving._buckets_hit([64, 128, 256, 512, 768, 1024], lo, hi)
    assert [b for b, _ in got] == want
    assert all(lo <= L <= hi and L <= b for b, L in got)


def test_check_sample_holds_the_longest_and_every_slot_that_served():
    import numpy as np
    n = 12
    led = serving.Ledger(n)
    led.fin[:10] = 5                       # the last two never finished
    led.slot[:10] = [0, 1, 2, 0, 1, 2, 0, 1, 2, 1]
    reqs = {"due": np.arange(n) * 0.5, "max_new": np.full(n, 4)}
    reqs["max_new"][4] = 9
    pick = serving.sample_for_check(np.random.default_rng(3), led, reqs,
                                    10.0, budget=1)
    assert pick[0] == 4 and len(pick) == 3 == len(set(pick))
    assert {int(led.slot[i]) for i in pick} == {0, 1, 2}
    full = serving.sample_for_check(np.random.default_rng(3), led, reqs,
                                    10.0, budget=10**6)
    assert sorted(full) == list(range(10))
