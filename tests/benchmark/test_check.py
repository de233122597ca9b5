"""The comparison that decides `correct`, on simulated histories: what a
sound server gives passes, and each broken guarantee comes out as a mismatch."""

import os

import numpy as np
import pytest

from benchmark import check
from benchmark.reference import serial
from tests.benchmark.helpers import keyspace, simulate

NONE = np.zeros(0, dtype=np.int64)
GLOBAL_FAULTS = ("lossy", "late", "serial")


def verdict(ks, ops, tainted=NONE):
    return check.check(ops, tainted, ks, serial.apply,
                       global_window=serial.global_window)


# `family`: the algorithms of a `global` family in the keyspace, whose keys
# are held to the stale-then-consistent rule, or None for no such family
@pytest.mark.parametrize("algorithms,family", [
    ("parity", None), ("leaky", None), ("token", None),
    ("parity", "token"), ("token", "leaky")])
@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("shape", ["slow_window", "fast_window"])
def test_sound_history_passes(algorithms, family, seed, shape):
    ks = keyspace(algorithms, family=family)
    kw = (dict(window_ms=21, lag_ms=21) if shape == "slow_window"
          else dict(window_ms=2, lag_ms=3, nops=40000))
    got = verdict(ks, simulate(ks, seed, **kw))
    assert got["mismatched_keys"] == 0, got["reports"]
    if (family, shape) == ("leaky", "fast_window"):
        # windows 2 ms apart and no told timestamp to order them by: the
        # guided search for a leaky GLOBAL key confirms what it can, and
        # leaves the rest undecided, never mismatched
        assert got["global_undecided_decisions"] == got["undecided_decisions"]
        assert got["global_checked_keys"] >= 16
        return
    assert got["undecided_decisions"] == 0, got["reports"]
    assert got["checked_decisions"] == got["followed_decisions"]
    if family:
        assert got["global_checked_decisions"] \
            == got["global_followed_decisions"] > 5000
        assert got["global_checked_keys"] == 64


@pytest.mark.parametrize("algorithms", ["parity", "leaky", "token"])
@pytest.mark.parametrize("fault", ["stale", "frozen", "altered",
                                   "lossy", "late", "serial"])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_broken_guarantee_is_a_mismatch(algorithms, fault, seed):
    # the last three break the GLOBAL family's guarantee, and leave the
    # serial keys sound: a dropped psum contribution, hits that land two
    # windows on, and an exactly serial server (each answer shows its own
    # hit), which has no witness under the rule either
    family = "token" if fault in GLOBAL_FAULTS else None
    ks = keyspace(algorithms, family=family)
    got = verdict(ks, simulate(ks, seed, fault))
    assert got["mismatched_keys"] > 0
    if family:
        assert got["global_mismatched_keys"] == got["mismatched_keys"] >= 32


# what `check` said of these histories on the parent of the PR that brought
# the `global` family (commit 04092c5): [followed, checked, checked keys,
# mismatched keys, undecided]; the accepted cells' keys get the same verdicts
PARENT_VERDICTS = {
    "parity|1|None": [
        20000,
        20000,
        5290,
        0,
        0
    ],
    "parity|3|stale": [
        20000,
        11051,
        5210,
        35,
        2742
    ],
    "parity|4|frozen": [
        20000,
        10893,
        4910,
        367,
        0
    ],
    "parity|5|altered": [
        20000,
        8071,
        4961,
        305,
        4251
    ],
    "leaky|1|None": [
        20000,
        20000,
        5290,
        0,
        0
    ],
    "leaky|3|stale": [
        20000,
        11657,
        5217,
        28,
        2742
    ],
    "leaky|4|frozen": [
        20000,
        13216,
        5157,
        120,
        0
    ],
    "leaky|5|altered": [
        20000,
        8071,
        4961,
        241,
        5895
    ],
    "token|1|None": [
        20000,
        20000,
        5290,
        0,
        0
    ],
    "token|3|stale": [
        20000,
        10758,
        5206,
        40,
        0
    ],
    "token|4|frozen": [
        20000,
        5849,
        4680,
        597,
        0
    ],
    "token|5|altered": [
        20000,
        8071,
        4961,
        370,
        0
    ],
    "recorded": [
        5147,
        5147,
        1,
        0,
        0
    ]
}


@pytest.mark.parametrize("case", sorted(PARENT_VERDICTS))
def test_verdicts_of_the_serial_family_are_what_they_were(case):
    if case == "recorded":
        rec = np.load(os.path.join(os.path.dirname(__file__),
                                   "recorded_hot_leaky_key.npz"))
        ks = keyspace("parity")               # rank 1: leaky, limit 10,000
        ops = {k: rec[k] for k in ("sent", "recv", "status", "remaining",
                                   "reset", "hint")}
        ops["rank"] = np.ones(len(rec["sent"]), dtype=np.int64)
    else:
        algorithms, seed, fault = case.split("|")
        ks = keyspace(algorithms)
        ops = simulate(ks, int(seed), None if fault == "None" else fault)
    got = check.check(ops, NONE, ks, serial.apply)
    assert [got[k] for k in ("followed_decisions", "checked_decisions",
                             "checked_keys", "mismatched_keys",
                             "undecided_decisions")] == PARENT_VERDICTS[case]


def global_ops(ks, rank, rows):
    """rows: (sent, recv, status, remaining, reset) of one key's answers."""
    cols = list(zip(*rows))
    return {"rank": np.full(len(rows), rank), "sent": np.array(cols[0], float),
            "recv": np.array(cols[1], float), "status": np.array(cols[2]),
            "remaining": np.array(cols[3]), "reset": np.array(cols[4]),
            "hint": np.zeros(len(rows), dtype=np.int64)}


@pytest.mark.parametrize("name,answers,mismatched", [
    # two requests of one window both read a new bucket; the next sees both
    ("one window, then its hits", [(0, 9, "L-1"), (0, 9, "L-1"), (20, 29, "L-2")], 0),
    # the same served serially: each answer shows its own hit
    ("serial", [(0, 9, "L-1"), (0, 9, "L-2"), (20, 29, "L-3")], 1),
    # a hit lost on the way: two were answered at L-2, one landed
    ("lost hit", [(0, 9, "L-1"), (0, 9, "L-1"), (20, 29, "L-2"), (20, 29, "L-2"),
                  (40, 49, "L-3")], 1),
    # a hit counted twice
    ("double hit", [(0, 9, "L-1"), (20, 29, "L-3")], 1),
    # staler than one window: sent after the other's reply was received, and
    # still reads what that one read
    ("too stale", [(0, 9, "L-1"), (0, 9, "L-1"), (20, 29, "L-2"), (40, 49, "L-2"),
                   (60, 69, "L-4")], 1),
])
def test_a_global_token_key_by_hand(name, answers, mismatched):
    ks = keyspace("parity", family="token")
    rank = ks.population + 2
    L, D = ks.limit(rank), ks.duration(rank)
    t = 1_700_000_000_000
    rows = [(t + a, t + b, 0, L - int(r[2:]), t + 5 + D) for a, b, r in answers]
    got = verdict(ks, global_ops(ks, rank, rows))
    assert got["mismatched_keys"] == mismatched, got["reports"]
    assert got["global_checked_decisions"] == (0 if mismatched else len(rows))


def test_windows_that_ask_for_more_than_is_left_are_refused_whole():
    """Three tokens left and windows of four requests: the rule refuses each
    window's hits whole, so every answer reads 3 until a window of three or
    fewer comes.  Windows of one or two among them would have landed."""
    ks = keyspace("parity", family="token")
    rank = ks.population + 2
    L, D = ks.limit(rank), ks.duration(rank)
    t = 1_700_000_000_000
    reset = t + 5 + D
    rows = [(t, t + 9, 0, L - 1, reset)] * (L - 3)      # one window: L-3 hits
    for w in range(1, 6):                               # five windows of four
        rows += [(t + 20 * w, t + 20 * w + 9, 0, 3, reset)] * 4
    rows += [(t + 120, t + 129, 0, 3, reset)] * 3       # the one that lands
    rows += [(t + 140, t + 149, 1, 0, reset)]
    assert verdict(ks, global_ops(ks, rank, rows))["mismatched_keys"] == 0
    # a window of two among them would have landed its hits
    rows[L - 3 + 4:L - 3 + 8] = [(t + 40, t + 49, 0, 3, reset)] * 2
    got = verdict(ks, global_ops(ks, rank, rows))
    assert got["global_checked_decisions"] == 0
    assert got["mismatched_keys"] + (got["undecided_decisions"] > 0) == 1


def test_a_global_key_needs_the_rule():
    ks = keyspace("parity", family="token")
    ops = simulate(ks, 1, nops=2000)
    with pytest.raises(ValueError, match="global_window"):
        check.check(ops, NONE, ks, serial.apply)


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
