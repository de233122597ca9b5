"""The comparison that decides `correct`.

The configuration guarantees that every decision equals the serial per-key
application of the acknowledged requests.  The clients see each answer and
when it was sent and received, not the order or the timestamp the server gave
it.  So for every followed key this module looks for a witness: an order of
the key's requests and a timestamp for each, inside that request's
[sent, received] span and never decreasing, under which the plain reference
(benchmark/reference) gives exactly the answers that were served.  The witness
is then replayed through the reference, answer by answer.

  token bucket   the answers themselves fix the order (reset time, then
                 remaining, falling), so the replay is the whole comparison;
  leaky bucket   an OVER_LIMIT answer names its timestamp (reset - rate), an
                 UNDER_LIMIT one does not, so a depth-first search orders the
                 requests that overlap in time and carries the feasible
                 interval of the last timestamp forward.

A key with no witness is a mismatch (`correct` false).  A key whose search ran
out of its budget, or whose witness the replay does not confirm (the search
treats the expiry clock loosely), is undecided: reported, and held to a share
of its own.  All requests carry hits = 1.
"""

import bisect
import math

import numpy as np

from benchmark.traffic import LEAKY, TOKEN

TOL_MS = 2          # clock granularity between the client's and server's ms
OVER = 1
INF = float("inf")
SAME = 8            # pending requests of one answer tried at one point
LOOK = 64           # requests past the first pending one looked at for one stranded


def _token_key(ops, L, D, apply):
    s, e, st, rem, rs = ops
    order = np.lexsort((e, -rem, st, rs))
    row, prev = None, -INF
    for i in order.tolist():
        lo, hi = math.floor(s[i]) - TOL_MS, math.ceil(e[i]) + TOL_MS
        if st[i] != OVER and rem[i] == L - 1:
            n = int(rs[i]) - D            # the request that made the bucket
            if n < lo or n < prev:
                return f"bucket made at {n}, before its request was sent"
        else:
            n = max(prev, lo)
        if n > hi:
            return (f"no timestamp left for remaining={int(rem[i])} "
                    f"reset={int(rs[i])}: needs >= {n}, answered by {hi}")
        row, got = apply(row, 1, L, D, TOKEN, int(n))
        want = (int(st[i]), L, int(rem[i]), int(rs[i]))
        if got != want:
            return f"served {want}, the reference says {got} at {int(n)}"
        prev = n
    return None


class _Leaky:
    """Witness search for one leaky key (see the module docstring)."""

    def __init__(self, ops, hint, L, D, budget, guide=None):
        s, e, st, rem, rs = ops
        self.L, self.D = L, D
        self.rate = rate = max(D // max(L, 1), 1)
        over = st == OVER
        n = np.where(over, rs - rate, 0)
        lo = np.floor(s).astype(np.int64) - TOL_MS
        hi = np.ceil(e).astype(np.int64) + TOL_MS
        ok_hint = (hint > 0) & (hint >= lo) & (hint <= hi)
        # Order of the first try.  A timestamp that is told (OVER_LIMIT, or
        # another answer of the same RPC: a hint, never a constraint) is
        # taken as it is.  One that is not told is guessed: answers of one
        # drain reach the clients together, so it takes the told timestamp
        # of the answer, to any key, that was received nearest to it
        # (`guide`); failing that, the receive time less the run's median
        # lag.  Requests of one timestamp were served as remaining falls.
        told = over | ok_hint
        h = np.where(over, n, np.where(ok_hint, hint, 0))
        lag = guide[2] if guide else 0.0
        guess = np.clip(np.floor(e - lag).astype(np.int64), lo, hi)
        if guide and len(guide[0]):
            recv, now = guide[0], guide[1]
            k = np.searchsorted(recv, e)
            left = np.clip(k - 1, 0, len(recv) - 1)
            right = np.clip(k, 0, len(recv) - 1)
            pick = np.where(np.abs(recv[left] - e) <= np.abs(recv[right] - e),
                            left, right)
            near = now[pick]
            guess = np.where((near >= lo) & (near <= hi), near, guess)
        h = np.where(told, h, guess)
        order = np.lexsort((-rem, over, h))
        self.lo, self.hi = lo[order].tolist(), hi[order].tolist()
        self.over, self.r = over[order].tolist(), rem[order].tolist()
        self.n, self.h = n[order].tolist(), h[order].tolist()
        self.N = len(order)
        self.over_pos = np.flatnonzero(over[order]).tolist()
        self.by_r = {}
        for pos in np.flatnonzero(~over[order]).tolist():
            self.by_r.setdefault(self.r[pos], []).append(pos)
        self.span = int(np.max(h - lo)) + 1 if self.N else 0
        self.budget = budget
        self.truncated = False
        self.resp = [(int(a), L, int(b), int(c)) for a, b, c in
                     zip(st[order], rem[order], rs[order])]

    def step(self, state, j, reinit):
        """State after request j, or None.  state = (R, flo, fhi, glo, ghi):
        remaining, the last timestamp's interval, the interval of the
        timestamp the expiry clock runs from (R < 0: no row yet)."""
        R, flo, fhi, glo, ghi = state
        lo, hi = max(self.lo[j], flo), self.hi[j]
        L, D, rate = self.L, self.D, self.rate
        if reinit:
            if self.over[j] or self.r[j] != L - 1:
                return None
            if R >= 0:
                lo = max(lo, glo + D + 1)
            if lo > hi:
                return None
            return (L - 1, lo, hi, lo, hi), (0, INF)
        if R < 0:
            return None
        hi = min(hi, ghi + D)             # the row has not expired
        if self.over[j]:
            n = self.n[j]
            if R != 0 or self.r[j] != 0 or n < lo or n > hi:
                return None
            if max(flo, n - rate + 1) > min(fhi, n):
                return None
            return (0, n, n, max(glo, n - D), ghi), (0, rate - 1)
        r = self.r[j]
        if r + 1 < L:
            leak = r + 1 - R
            if leak < 0:
                return None
            a, b = leak * rate, leak * rate + rate - 1
        else:
            a, b = (L - R) * rate, INF
        lo, hi = max(lo, flo + a), min(hi, fhi + b)
        if lo > hi:
            return None
        if r > 0:                          # a grant restarts the expiry clock
            return (r, lo, hi, lo, hi), (a, b)
        return (r, lo, hi, max(glo, lo - D), ghi), (a, b)

    def earliest(self, state, j):
        """The smallest timestamp an UNDER_LIMIT request j could take after
        `state` by its answer alone, or None if its answer cannot follow."""
        R, flo = state[0], state[1]
        r = self.r[j]
        if r == self.L - 1:
            return max(self.lo[j], flo)        # a full bucket: leak or re-init
        if R < 0 or r + 1 < R:
            return None
        return max(self.lo[j], flo + (r + 1 - R) * self.rate)

    def pending(self, positions, first, done, limit_hi):
        """Pending requests of one list (positions rise with the told or
        guessed timestamp), from `first` on, that can still come before the
        first pending request's answer was received."""
        k = bisect.bisect_left(positions, first)
        lo, h, span = self.lo, self.h, self.span
        while k < len(positions):
            j = positions[k]
            k += 1
            if done[j]:
                continue
            if h[j] - span > limit_hi:
                return
            if lo[j] <= limit_hi:
                yield j

    def options(self, first, done, state):
        """What may come next, likeliest first.  The answers themselves say
        which requests can follow a state with R tokens left: OVER_LIMIT only
        at R = 0, UNDER_LIMIT with remaining r only for r >= R - 1 (each
        token above that is a token leaked, a `rate` of time).  So the
        candidates are looked up by their answer: the next pending OVER_LIMIT,
        and for each r from R - 1 up to what the time allows the first few
        pending requests that were answered r.  Whoever comes next must also
        be able to come before the first pending request's answer was
        received, since timestamps never decrease: that bounds the look."""
        over, R = self.over, state[0]
        e1 = None if over[first] else self.earliest(state, first)
        j_over = None
        if R == 0:
            j_over = self.pending_from(self.over_pos, first, done)
        if j_over is not None and (over[first] or e1 is None
                                   or self.n[j_over] < e1):
            yield j_over, False
            j_over = None
        if e1 is not None:
            if self.r[first] + 1 >= R >= 0:
                yield first, False
            if self.r[first] == self.L - 1:
                yield first, True
        # by answer, around the first pending request
        limit_hi = self.hi[first]
        top = self.L - 1
        if R < 0:
            values = [top]
        else:
            reach = int((limit_hi - state[1]) // self.rate) + 1
            values = list(range(max(R - 1, 0), min(R + reach, top) + 1))
            if not values or values[-1] != top:
                values.append(top)        # an expired row starts full again
        cands = []
        for r in values:
            got = 0
            for j in self.pending(self.by_r.get(r, ()), first, done, limit_hi):
                if j == first:
                    continue
                got += 1
                if got > SAME:
                    self.truncated = True
                    break
                cands.append(j)
        cands.sort()
        for j in cands:
            if R >= 0:
                yield j, False
            if self.r[j] == top:
                yield j, True
        if j_over is not None:
            yield j_over, False

    def pending_from(self, positions, first, done):
        k = bisect.bisect_left(positions, first)
        while k < len(positions):
            if not done[positions[k]]:
                return positions[k]
            k += 1
        return None

    def strands(self, state, j, first, done):
        """Would taking j, which leads to `state`, leave an older pending
        request with no timestamp left?  Timestamps never decrease and tokens
        come back only with time, so a request whose answer needs more tokens
        than `state` holds needs that much leak before its own answer was
        received (or an expired row).  Such a branch is dead already: seeing
        it here keeps the backtracking near the choice that was wrong."""
        R, flo, _, glo, _ = state
        lo, hi, r, over = self.lo, self.hi, self.r, self.over
        top, rate, expired = self.L - 1, self.rate, max(flo, glo + self.D + 1)
        for k in range(first, min(first + LOOK, self.N)):
            if lo[k] > flo:
                break
            if done[k] or k == j:
                continue
            if over[k]:
                if self.n[k] < flo:
                    return True
            elif (flo + max(0, r[k] + 1 - R) * rate > hi[k]
                  and (r[k] != top or expired > hi[k])):
                return True
        return False

    def search(self):
        N = self.N
        if any(o and r for o, r in zip(self.over, self.r)):
            return "none", None       # refused (hits = 1) yet tokens left
        done = bytearray(N)
        state = (-1, -INF, INF, -INF, INF)
        first, steps = 0, 0
        stack = []          # (request, state before, first before, options)
        chosen = []         # (request, interval after, gap to the one before)
        if N == 0:
            return "ok", chosen
        gen = self.options(first, done, state)
        while True:
            got = None
            for j, reinit in gen:
                steps += 1
                got = self.step(state, j, reinit)
                if got is not None and self.strands(got[0], j, first, done):
                    got = None
                if got is not None:
                    break
            if steps > self.budget:
                return "undecided", None
            if got is None:
                if not stack:
                    return ("undecided" if self.truncated else "none"), None
                j, state, first, gen = stack.pop()
                done[j] = 0
                chosen.pop()
                continue
            stack.append((j, state, first, gen))
            done[j] = 1
            state, gap = got
            chosen.append((j, state[1], state[2], gap))
            while first < N and done[first]:
                first += 1
            if first == N:
                return "ok", chosen
            gen = self.options(first, done, state)

    def replay(self, chosen, apply):
        """Fix a timestamp for each request of the witness, last to first,
        and run the reference over them.  The search carried the expiry clock
        as a loose interval; here each grant's timestamp is held to what the
        requests after it need: not yet expired at each of them, expired at a
        re-initialisation."""
        k = len(chosen)
        D, L = self.D, self.L
        nows = [0] * k
        nxt, gap = None, None
        need_lo, need_hi = -INF, INF      # for the grant before these requests
        for i in range(k - 1, -1, -1):
            j, lo, hi, mygap = chosen[i]
            if nxt is not None:
                a, b = gap
                if b != INF:
                    lo = max(lo, nxt - b)
                hi = min(hi, nxt - a)
            reinit = mygap[1] == INF and mygap[0] == 0
            grant = not self.over[j] and (self.r[j] > 0 or reinit)
            if grant:
                lo, hi = max(lo, need_lo), min(hi, need_hi)
            if lo > hi:
                return "the witness's intervals do not close"
            nows[i] = nxt = int(hi)
            gap = mygap
            if grant:
                need_lo, need_hi = -INF, INF
            if reinit:
                need_hi = nxt - D - 1
            else:
                need_lo = max(need_lo, nxt - D)
        row = None
        for (j, _, _, _), n in zip(chosen, nows):
            row, got = apply(row, 1, L, D, LEAKY, n)
            if got != self.resp[j]:
                return f"served {self.resp[j]}, the reference says {got} at {n}"
        return None


def _leaky_key(ops, hint, L, D, apply, budget, guide):
    w = _Leaky(ops, hint, L, D, budget, guide)
    verdict, chosen = w.search()
    if verdict == "none":
        return "none", "no order and timestamps explain the served answers"
    if verdict == "undecided":
        return "undecided", "search budget spent"
    why = w.replay(chosen, apply)
    if why:
        return "undecided", why
    return "ok", None


def _guide(ops, keyspace):
    """Every told timestamp of the run beside when its answer was received,
    by receive time, and the median of their difference: what a request whose
    timestamp is not told is guessed from.  Told are the leaky OVER_LIMIT
    answers (reset - rate) and the answers whose RPC held one (`hint`).
    Only orders the search's first try."""
    rank = ops["rank"]
    over = ((keyspace.algos_of(rank) == LEAKY) & (ops["status"] == OVER)
            & (ops["reset"] > 0))
    rate = np.maximum(keyspace.duration_ms // keyspace.limits_of(rank), 1)
    now = np.where(over, ops["reset"] - rate, ops["hint"])
    told = over | (ops["hint"] > 0)
    if not told.any():
        return None
    recv, now = ops["recv"][told], now[told]
    by = np.argsort(recv)
    return recv[by], now[by], float(np.median(recv - now))


def check(ops, tainted, keyspace, apply, max_report=5):
    """ops: dict of equal-length arrays rank, sent, recv (epoch ms), status,
    remaining, reset, hint.  Returns the numbers compared and some words on
    the first keys that failed."""
    rank = ops["rank"]
    out = {"followed_decisions": int(len(rank)), "checked_decisions": 0,
           "checked_keys": 0, "mismatched_keys": 0, "undecided_decisions": 0,
           "tainted_keys": 0, "reports": []}
    if not len(rank):
        return out
    bad = set(int(x) for x in np.unique(tainted))
    order = np.argsort(rank, kind="stable")
    r_sorted = rank[order]
    cuts = np.flatnonzero(np.diff(r_sorted)) + 1
    D = keyspace.duration_ms
    guide = _guide(ops, keyspace)
    for idx in np.split(order, cuts):
        rk = int(rank[idx[0]])
        if rk in bad:
            out["tainted_keys"] += 1
            continue
        L, algo = keyspace.limit(rk), keyspace.algo(rk)
        cols = (ops["sent"][idx], ops["recv"][idx],
                ops["status"][idx].astype(np.int64),
                ops["remaining"][idx], ops["reset"][idx])
        if algo == TOKEN:
            why = _token_key(cols, L, D, apply)
            verdict = "none" if why else "ok"
        else:
            verdict, why = _leaky_key(cols, ops["hint"][idx], L, D, apply,
                                      100 * len(idx) + 4000, guide)
        if verdict == "ok":
            out["checked_keys"] += 1
            out["checked_decisions"] += len(idx)
        elif verdict == "none":
            out["mismatched_keys"] += 1
        else:
            out["undecided_decisions"] += len(idx)
        if why and len(out["reports"]) < max_report:
            out["reports"].append(
                f"rank {rk} ({'leaky' if algo else 'token'}, limit {L}, "
                f"{len(idx)} answers): {verdict}: {why}")
    return out
