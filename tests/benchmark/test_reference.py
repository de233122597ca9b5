"""The plain reference against hand-worked cases."""

import pytest

from benchmark.reference import serial
from benchmark.reference.serial import (LEAKY_BUCKET, OVER_LIMIT,
                                        TOKEN_BUCKET, UNDER_LIMIT, apply)

T0 = 1_700_000_000_000


def run(algo, limit, duration, steps):
    row, out = None, []
    for hits, now in steps:
        row, resp = apply(row, hits, limit, duration, algo, now)
        out.append(resp)
    return out


def test_token_counts_down_then_refuses():
    got = run(TOKEN_BUCKET, 3, 1000, [(1, T0), (1, T0 + 1), (1, T0 + 2),
                                      (1, T0 + 3)])
    assert got == [(UNDER_LIMIT, 3, 2, T0 + 1000),
                   (UNDER_LIMIT, 3, 1, T0 + 1000),
                   (UNDER_LIMIT, 3, 0, T0 + 1000),
                   (OVER_LIMIT, 3, 0, T0 + 1000)]


def test_token_resets_only_after_expiry():
    got = run(TOKEN_BUCKET, 1, 1000, [(1, T0), (1, T0 + 1000), (1, T0 + 1001)])
    assert got[1] == (OVER_LIMIT, 1, 0, T0 + 1000)       # expire == now: live
    assert got[2] == (UNDER_LIMIT, 1, 0, T0 + 2001)      # a new bucket


def test_token_overask_rejects_without_taking():
    got = run(TOKEN_BUCKET, 5, 1000, [(3, T0), (3, T0 + 1), (2, T0 + 2)])
    assert got[1] == (OVER_LIMIT, 5, 2, T0 + 1000)
    assert got[2] == (UNDER_LIMIT, 5, 0, T0 + 1000)


def test_token_read_does_not_take():
    got = run(TOKEN_BUCKET, 5, 1000, [(1, T0), (0, T0 + 1), (1, T0 + 2)])
    assert [r[2] for r in got] == [4, 4, 3]


def test_leaky_leaks_one_token_per_rate():
    # limit 10 per 1000 ms: one token back every 100 ms
    got = run(LEAKY_BUCKET, 10, 1000, [(1, T0), (1, T0 + 50), (1, T0 + 250)])
    assert got == [(UNDER_LIMIT, 10, 9, 0), (UNDER_LIMIT, 10, 8, 0),
                   (UNDER_LIMIT, 10, 9, 0)]              # 8 + 2 leaked - 1


def test_leaky_hit_restarts_the_leak_clock():
    # hits every 60 ms never let 100 ms pass: nothing ever leaks back
    steps = [(1, T0 + 60 * i) for i in range(12)]
    got = run(LEAKY_BUCKET, 10, 1000, steps)
    assert [r[2] for r in got[:10]] == list(range(9, -1, -1))
    assert got[10] == (OVER_LIMIT, 10, 0, T0 + 600 + 100)
    assert got[11] == (OVER_LIMIT, 10, 0, T0 + 660 + 100)


def test_leaky_over_limit_names_its_timestamp():
    got = run(LEAKY_BUCKET, 1, 1000, [(1, T0), (1, T0 + 10)])
    assert got[1] == (OVER_LIMIT, 1, 0, T0 + 10 + 1000)  # reset - rate == now


def test_leaky_never_fills_past_the_limit():
    got = run(LEAKY_BUCKET, 10, 1000, [(1, T0), (1, T0 + 900)])
    assert got[1] == (UNDER_LIMIT, 10, 9, 0)


def test_leaky_row_expires_a_duration_after_its_last_grant():
    # drained at T0+2; refusals do not renew the row; at T0+1003 it is gone
    got = run(LEAKY_BUCKET, 2, 1000, [(1, T0), (1, T0 + 2), (1, T0 + 300),
                                      (1, T0 + 1001)])
    assert got[2] == (OVER_LIMIT, 2, 0, T0 + 300 + 500)
    assert got[3] == (UNDER_LIMIT, 2, 1, 0)


def test_algorithm_switch_starts_over():
    row, _ = apply(None, 1, 5, 1000, TOKEN_BUCKET, T0)
    row, resp = apply(row, 1, 5, 1000, LEAKY_BUCKET, T0 + 1)
    assert resp == (UNDER_LIMIT, 5, 4, 0) and row.algo == LEAKY_BUCKET


def test_other_algorithms_are_refused():
    with pytest.raises(ValueError):
        apply(None, 1, 5, 1000, 2, T0)


def test_store_keeps_keys_apart():
    s = serial.SerialStore()
    assert s.hit("a", 1, 2, 1000, TOKEN_BUCKET, T0)[2] == 1
    assert s.hit("b", 1, 2, 1000, TOKEN_BUCKET, T0)[2] == 1
    assert s.hit("a", 1, 2, 1000, TOKEN_BUCKET, T0)[2] == 0


@pytest.mark.parametrize("config", ["mixed-10m-1chip", "leaky-1m-1chip"])
def test_each_configuration_has_its_reference(config):
    from benchmark import harness
    ref = harness.Bench().reference(config)
    assert ref(None, 1, 5, 1000, LEAKY_BUCKET, T0)[1] == (UNDER_LIMIT, 5, 4, 0)
    if config.startswith("leaky"):
        with pytest.raises(ValueError):
            ref(None, 1, 5, 1000, TOKEN_BUCKET, T0)


def test_the_accepted_references_state_no_global_rule():
    from benchmark import harness
    bench = harness.Bench()
    for config in ("mixed-10m-1chip", "leaky-1m-1chip"):
        assert set(bench.reference_functions(config)) == {"apply"}


def test_global_window_reads_before_and_lands_once():
    # three requests of one window on a new token key: each answers as if it
    # alone had made the bucket; then the three hits land together
    ask = (1, 5, 1000, TOKEN_BUCKET)
    row, got = serial.global_window(None, [ask] * 3, T0)
    assert got == [(UNDER_LIMIT, 5, 4, T0 + 1000)] * 3 and row.remaining == 2
    # the next window reads 2, whatever its own hits; its two hits land
    row, got = serial.global_window(row, [ask] * 2, T0 + 10)
    assert got == [(UNDER_LIMIT, 5, 2, T0 + 1000)] * 2 and row.remaining == 0
    # an empty bucket refuses, and stays as it is
    row, got = serial.global_window(row, [ask], T0 + 20)
    assert got == [(OVER_LIMIT, 5, 0, T0 + 1000)] and row.remaining == 0
    # a window that asks for more than is left lands nothing
    row, _ = serial.global_window(None, [ask] * 3, T0)
    row, got = serial.global_window(row, [ask] * 3, T0 + 10)
    assert got == [(UNDER_LIMIT, 5, 2, T0 + 1000)] * 3 and row.remaining == 2
    # expired (expire < now): answered, and made, anew
    row, got = serial.global_window(row, [ask], T0 + 1001)
    assert got == [(UNDER_LIMIT, 5, 4, T0 + 2001)] and row.remaining == 4


@pytest.mark.parametrize("algo", [TOKEN_BUCKET, LEAKY_BUCKET, "mixed"])
@pytest.mark.parametrize("seed", [1, 2, 3_000_000_007])
def test_global_window_is_the_programs_own_statement_of_the_rule(algo, seed):
    """Seeded random windows through the rule and through chip_smoke.py's
    oracle of the same rule (the program's own, over the program's serial
    oracle): misses, expiry inside the sequence (durations of 40-300 ms
    against windows 0-90 ms apart), windows that take a key over its limit
    (limits of 3-12, up to 6 requests of up to 3 hits a window), reads
    (hits = 0), and with `mixed` a key asked for under the other algorithm."""
    import random

    import chip_smoke
    from gubernator_tpu import Behavior, RateLimitReq
    rng = random.Random(seed)
    oracle = chip_smoke.Oracle()
    rows = {}
    now = T0
    seen = {"over": 0, "expired": 0, "miss": 0, "answers": 0}
    for _ in range(400):
        now += rng.choice((0, 1, 7, 30, 90))
        window = []
        for _ in range(rng.randint(1, 6)):
            key = f"k{rng.randint(1, 5)}"
            a = algo if algo != "mixed" else rng.choice((0, 0, 0, 1))
            limit = 3 + 3 * (hash(key) % 4) if algo != "mixed" else 6
            window.append(RateLimitReq(
                name="n", unique_key=key, hits=rng.choice((0, 1, 1, 2, 3)),
                limit=limit, duration=rng.choice((40, 300)), algorithm=a,
                behavior=Behavior.GLOBAL))
        want = oracle.global_window(window, now)
        got = [None] * len(window)
        by_key = {}
        for i, r in enumerate(window):
            by_key.setdefault(r.unique_key, []).append(i)
        for key, at in by_key.items():
            old = rows.get(key)
            seen["miss"] += old is None
            seen["expired"] += old is not None and old.expire < now
            rows[key], answers = serial.global_window(
                old, [(window[i].hits, window[i].limit, window[i].duration,
                       int(window[i].algorithm)) for i in at], now)
            for i, resp in zip(at, answers):
                got[i] = resp
        assert got == [tuple(w) for w in want]
        seen["over"] += sum(g[0] == OVER_LIMIT for g in got)
        seen["answers"] += len(got)
        for key, row in rows.items():
            theirs = oracle.grows.get(
                RateLimitReq(name="n", unique_key=key).hash_key())
            if row is None or theirs is None:
                assert row is None and theirs is None
                continue
            assert (row.limit, row.duration, row.remaining, row.tstamp,
                    row.expire, row.algo) == (
                theirs.limit, theirs.duration, theirs.remaining, theirs.tstamp,
                theirs.expire, theirs.algo)
    assert seen["over"] > 50 and seen["expired"] > 20 and seen["miss"] >= 5
