"""The comparison that decides `correct`, on simulated histories: what a
sound server gives passes, and each broken guarantee comes out as a mismatch."""

import numpy as np
import pytest

from benchmark import check
from benchmark.reference import serial
from tests.benchmark.helpers import keyspace, simulate

NONE = np.zeros(0, dtype=np.int64)


def verdict(ks, ops, tainted=NONE):
    return check.check(ops, tainted, ks, serial.apply)


@pytest.mark.parametrize("algorithms", ["parity", "leaky", "token"])
@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("shape", ["slow_window", "fast_window"])
def test_sound_history_passes(algorithms, seed, shape):
    ks = keyspace(algorithms)
    kw = (dict(window_ms=21, lag_ms=21) if shape == "slow_window"
          else dict(window_ms=2, lag_ms=3, nops=40000))
    got = verdict(ks, simulate(ks, seed, **kw))
    assert got["mismatched_keys"] == 0, got["reports"]
    assert got["undecided_decisions"] == 0, got["reports"]
    assert got["checked_decisions"] == got["followed_decisions"]


@pytest.mark.parametrize("algorithms", ["parity", "leaky", "token"])
@pytest.mark.parametrize("fault", ["stale", "frozen", "altered"])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_broken_guarantee_is_a_mismatch(algorithms, fault, seed):
    ks = keyspace(algorithms)
    got = verdict(ks, simulate(ks, seed, fault))
    assert got["mismatched_keys"] > 0


def test_short_durations_expire_and_still_pass():
    ks = keyspace("parity", duration_ms=3000, population=2000)
    got = verdict(ks, simulate(ks, 7, nops=30000, span_ms=20000))
    # buckets expire and are made anew many times over: never a mismatch.
    # (At 3 s a limit of 10,000 clamps the leak to a token a millisecond;
    # on the hottest key the search may then spend its budget: undecided.)
    assert got["mismatched_keys"] == 0, got["reports"]
    assert got["checked_decisions"] >= 0.75 * got["followed_decisions"]


def test_tainted_keys_leave_the_comparison():
    ks = keyspace("token")
    ops = simulate(ks, 8, "altered")
    bad = verdict(ks, ops)
    assert bad["mismatched_keys"] > 0
    every = np.unique(ops["rank"])
    got = verdict(ks, ops, tainted=every)
    assert got["mismatched_keys"] == 0 and got["tainted_keys"] == len(every)


def test_an_answer_outside_its_request_span_is_a_mismatch():
    ks = keyspace("token")
    ops = simulate(ks, 9, nops=2000)
    # the first answer claims a bucket made a minute before it was asked for
    ops["reset"][0] -= 60000
    assert verdict(ks, ops)["mismatched_keys"] >= 1


def test_two_requests_served_the_same_token():
    ks = keyspace("token")
    rank = 2                                  # a token key
    L, D = ks.limit(rank), ks.duration_ms
    t = 1_700_000_000_000.0
    ops = {"rank": np.array([rank, rank]), "sent": np.array([t, t]),
           "recv": np.array([t + 5, t + 5]), "status": np.array([0, 0]),
           "remaining": np.array([L - 1, L - 1]),
           "reset": np.array([int(t) + 2 + D] * 2), "hint": np.array([0, 0])}
    assert verdict(ks, ops)["mismatched_keys"] == 1
    ops["remaining"] = np.array([L - 1, L - 2])
    assert verdict(ks, ops)["mismatched_keys"] == 0


def test_leaky_order_is_found_when_answers_arrive_shuffled():
    ks = keyspace("leaky")
    rank = 1                                  # limit 10000: 6 ms a token
    L = ks.limit(rank)
    t = 1_700_000_000_000.0
    # one drain at t+10 serves four concurrent requests: L-1, L-2, L-3, L-4
    rem = np.array([L - 3, L - 1, L - 4, L - 2])
    ops = {"rank": np.full(4, rank), "sent": np.full(4, t),
           "recv": t + 20 + np.arange(4.0), "status": np.zeros(4, dtype=int),
           "remaining": rem, "reset": np.zeros(4, dtype=int),
           "hint": np.zeros(4, dtype=int)}
    got = verdict(ks, ops)
    assert got["mismatched_keys"] == 0 and got["checked_decisions"] == 4
    # but a token handed out twice, with no time for it to leak back (a
    # token takes 6 ms; every answer came within 1), has no explanation
    ops["remaining"] = np.array([L - 1, L - 1, L - 2, L - 3])
    ops["recv"] = np.full(4, t + 1)
    assert verdict(ks, ops)["mismatched_keys"] == 1


def test_a_recorded_hot_leaky_key_is_decided_within_the_budget():
    """5,147 answers of the hottest leaky key (limit 10,000 a minute, never
    over it, so no answer tells its timestamp) as the edge mix's clients
    recorded them against the reference served in windows of 25 ms.  One
    wrongly guessed drain early on used to cost the whole budget: the search
    saw the dead end only thousands of choices later."""
    import os
    rec = np.load(os.path.join(os.path.dirname(__file__),
                               "recorded_hot_leaky_key.npz"))
    cols = (rec["sent"], rec["recv"], rec["status"].astype(np.int64),
            rec["remaining"], rec["reset"])
    guide = (rec["guide_recv"], rec["guide_now"], float(rec["guide_lag"]))
    w = check._Leaky(cols, rec["hint"], 10000, 60000, 100 * len(cols[0]) + 4000,
                     guide)
    got, chosen = w.search()
    assert got == "ok"
    assert w.replay(chosen, serial.apply) is None
    # the same answers with one of them altered have no witness
    rem = rec["remaining"].copy()
    rem[len(rem) // 2] -= 300
    w = check._Leaky(cols[:3] + (rem, cols[4]), rec["hint"], 10000, 60000,
                     100 * len(rem) + 4000, guide)
    assert w.search()[0] != "ok"
