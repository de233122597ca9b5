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
