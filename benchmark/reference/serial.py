"""Plain serial reference of the rate-limit semantics the cells serve.

One request against one stored row, in python integers: token bucket and
leaky bucket as the upstream algorithms.go defines them, with the three
divergences this repository documents (an algorithm switch re-initialises,
a leaky row expires at now + duration, a leaky rate is clamped to >= 1 ms).
It imports nothing of the program under test: the benchmark's `correct` is
a comparison of the served answers with `apply` below, request by request.

A row is None on a miss; an expired row (expire < now) counts as a miss.
The response is (status, limit, remaining, reset_time).

`global_window` states the other guarantee a configuration may give, Behavior
GLOBAL on a mesh of chips: stale, then consistent.  Every answer of one
window is a read of the row as it stood before the window, and the window's
hits are summed and applied once after it.
"""

TOKEN_BUCKET = 0
LEAKY_BUCKET = 1
UNDER_LIMIT = 0
OVER_LIMIT = 1


class Row:
    __slots__ = ("limit", "duration", "remaining", "tstamp", "expire", "algo")

    def __init__(self, limit, duration, remaining, tstamp, expire, algo):
        self.limit = limit
        self.duration = duration
        self.remaining = remaining
        self.tstamp = tstamp
        self.expire = expire
        self.algo = algo

    def copy(self):
        return Row(self.limit, self.duration, self.remaining, self.tstamp,
                   self.expire, self.algo)


def _init(hits, limit, duration, algo, now):
    over = hits > limit
    remaining = 0 if over else limit - hits
    if algo == LEAKY_BUCKET:
        tstamp, reset = now, 0
    else:
        tstamp, reset = now + duration, now + duration
    row = Row(limit, duration, remaining, tstamp, now + duration, algo)
    return row, (OVER_LIMIT if over else UNDER_LIMIT, limit, remaining, reset)


def _token(row, hits):
    left = row.remaining
    if left == 0:
        return row, (OVER_LIMIT, row.limit, 0, row.tstamp)
    if hits == 0:
        return row, (UNDER_LIMIT, row.limit, left, row.tstamp)
    if hits > left:
        return row, (OVER_LIMIT, row.limit, left, row.tstamp)
    row.remaining = left - hits
    return row, (UNDER_LIMIT, row.limit, left - hits, row.tstamp)


def _leaky(row, hits, req_limit, req_duration, now):
    rate = max(row.duration // max(req_limit, 1), 1)
    leak = (now - row.tstamp) // rate
    left = row.remaining + min(leak, row.limit - row.remaining)
    row.remaining = left
    if hits != 0:
        row.tstamp = now
    if left == 0:
        return row, (OVER_LIMIT, row.limit, 0, now + rate)
    if hits == left:
        row.remaining = 0
        return row, (UNDER_LIMIT, row.limit, 0, 0)
    if hits > left:
        return row, (OVER_LIMIT, row.limit, left, now + rate)
    if hits == 0:
        return row, (UNDER_LIMIT, row.limit, left, 0)
    row.remaining = left - hits
    row.expire = now + req_duration
    return row, (UNDER_LIMIT, row.limit, left - hits, 0)


def apply(row, hits, limit, duration, algo, now):
    """Apply one request; returns (new row, response)."""
    if algo not in (TOKEN_BUCKET, LEAKY_BUCKET):
        raise ValueError(f"the reference covers token and leaky only: {algo}")
    if row is None or row.expire < now or row.algo != algo:
        return _init(hits, limit, duration, algo, now)
    if algo == LEAKY_BUCKET:
        return _leaky(row, hits, limit, duration, now)
    return _token(row, hits)


def global_window(row, requests, now):
    """One GLOBAL key through one window.  `requests` is the window's
    [(hits, limit, duration, algo), ...] for that key, `now` the window's one
    timestamp.  Every answer is the read (hits = 0) of the row as it stood
    before the window; a missing, expired or other-algorithm row answers as
    if made by the request's own hits.  Then the window's summed hits are
    applied once, under the last request's limit and duration.  Returns
    (new row, [response, ...])."""
    out, summed, last = [], 0, None
    for hits, limit, duration, algo in requests:
        live = row is not None and row.expire >= now and row.algo == algo
        _, resp = apply(row.copy() if live else None, 0 if live else hits,
                        limit, duration, algo, now)
        out.append(resp)
        summed += hits
        last = (limit, duration, algo)
    if summed:
        row, _ = apply(row, summed, last[0], last[1], last[2], now)
    return row, out


class SerialStore:
    """The whole keyspace as a dict of rows: the straightforward server."""

    def __init__(self):
        self.rows = {}

    def hit(self, key, hits, limit, duration, algo, now):
        row, resp = apply(self.rows.get(key), hits, limit, duration, algo, now)
        self.rows[key] = row
        return resp
