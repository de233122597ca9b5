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

Keys of the keyspace's `global` family (ranks above `population`) are held to
another guarantee, Behavior GLOBAL on a mesh: stale, then consistent.  Every
answer of one window is the read of the row as it stood before the window,
and the window's summed hits land once after it (`global_window` of the
configuration's reference).  The witness for such a key is a split of its
requests into consecutive windows with non-decreasing timestamps, each inside
every member's [sent - TOL_MS, received + TOL_MS], under which that rule,
replayed window by window, gives exactly the served answers.  It is a
different guarantee, not a looser one: the answers of an exactly serial
server (each shows its own hit) have no witness under it either.

  token bucket   the answers name their window.  All answers of one window
                 are equal, a bucket's reset time says which bucket, and
                 within a bucket `remaining` only falls, so the windows come
                 in the order (reset, -remaining).  A level (one bucket, one
                 `remaining` R) of n answers followed by the level R' holds
                 one window of exactly R - R' requests, the one whose hits
                 landed: the psum lost no hit and counted none twice.  More
                 answers than that can only be windows that asked for more
                 than was left (more than R requests each), whose hits the
                 rule refuses whole.  So R - R' < n <= 2R - R' has no witness
                 by counting, n = R - R' needs one timestamp common to all n
                 spans, and only a level with refused windows is searched: a
                 partition of its requests, by receive time, into runs of
                 more than R and a last run of R - R', each run with a common
                 timestamp, by dynamic programming over the run's end.  The
                 window that made a bucket names its timestamp (reset -
                 duration).
  leaky bucket   the answers do not fix the order: a read shows what has
                 leaked back, so `remaining` rises and falls, and only an
                 OVER_LIMIT answer tells its timestamp.  The order is taken
                 from the told or guessed timestamps (as for a serial leaky
                 key, `guide`); requests that follow each other there with
                 equal answers are one window, split where their spans share
                 no timestamp.  What is searched is only each window's
                 timestamp: the leak between two windows fixes their distance
                 to within one token's time, carried forward as an interval
                 and fixed last to first.  The rule's replay decides; a key
                 that it does not confirm is undecided, never a mismatch.
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


def _guessed(e, lo, hi, guide):
    """A timestamp for each request that tells none: answers of one drain
    reach the clients together, so the told timestamp of the answer, to any
    key, that was received nearest to it (`guide`); failing that, the receive
    time less the run's median lag.  Held inside the request's span."""
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
    return guess


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
        h = np.where(told, h, _guessed(e, lo, hi, guide))
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


def _leaky_told(ops, keyspace):
    """Which answers are a leaky bucket's OVER_LIMIT, and the timestamp each
    of those tells (reset - rate)."""
    rank = ops["rank"]
    over = ((keyspace.algos_of(rank) == LEAKY) & (ops["status"] == OVER)
            & (ops["reset"] > 0))
    rate = np.maximum(keyspace.durations_of(rank) // keyspace.limits_of(rank), 1)
    return over, ops["reset"] - rate


def _guide(ops, keyspace):
    """Every told timestamp of the run beside when its answer was received,
    by receive time, and the median of their difference: what a request whose
    timestamp is not told is guessed from.  Told are the leaky OVER_LIMIT
    answers (reset - rate) and the answers whose RPC held one (`hint`).
    Only orders the search's first try."""
    over, at = _leaky_told(ops, keyspace)
    now = np.where(over, at, ops["hint"])
    told = over | (ops["hint"] > 0)
    if not told.any():
        return None
    recv, now = ops["recv"][told], now[told]
    by = np.argsort(recv)
    return recv[by], now[by], float(np.median(recv - now))


# ------------------------------------------------------- the GLOBAL family


def _spans(s, e):
    return (np.floor(s).astype(np.int64) - TOL_MS,
            np.ceil(e).astype(np.int64) + TOL_MS)


def _runs(lo, hi, t_prev, big, last, steps):
    """Requests of one level in receive order, as consecutive runs with a
    common timestamp each, never decreasing from `t_prev`: every run but the
    last of more than `big` requests, the last of exactly `last` (None: of
    any size, even none).  Returns [(start, end, timestamp)] with the
    earliest last timestamp there is among such partitions, or None."""
    n = len(lo)
    best = [None] * (n + 1)       # end timestamp and cut of the prefix [0, i)
    best[0] = (t_prev, None)
    for i in range(1, n + 1):
        mlo, mhi, got = -INF, INF, None
        for j in range(i - 1, -1, -1):
            mlo, mhi = max(mlo, lo[j]), min(mhi, hi[j])
            if mlo > mhi:
                break
            steps[0] += 1
            if best[j] is None:
                continue
            size = i - j
            if i == n and last is not None:
                if size > last:
                    break
                if size != last:
                    continue
            elif size <= big and not (i == n and last is None):
                continue
            t = max(best[j][0], mlo)
            if t <= mhi and (got is None or t < got[0]):
                got = (t, j)
        best[i] = got
    if best[n] is None:
        return None
    out, i = [], n
    while i > 0:
        t, j = best[i]
        out.append((j, i, t))
        i = j
    return out[::-1]


def _global_token_key(ops, L, D, rule, budget):
    """Witness for one token key of the GLOBAL family (module docstring).
    Returns (verdict, why)."""
    s, e, st, rem, rs = ops
    lo, hi = _spans(s, e)
    if np.any((st == OVER) != (rem == 0)) and L > 1:
        return "none", "a read says OVER_LIMIT with tokens left, or the reverse"
    order = np.lexsort((e, st, -rem, rs))
    lo, hi = lo[order], hi[order]
    st, rem, rs = st[order], rem[order], rs[order]
    n = len(order)
    # levels: runs of equal (reset, remaining, status)
    cut = np.flatnonzero((np.diff(rs) != 0) | (np.diff(rem) != 0)
                         | (np.diff(st) != 0)) + 1
    bounds = [0] + cut.tolist() + [n]
    windows = []                  # (members' positions, timestamp)
    t_prev, exact, steps = -INF, True, [0]
    for a, b in zip(bounds[:-1], bounds[1:]):
        R, reset, status = int(rem[a]), int(rs[a]), int(st[a])
        nxt = b < n and int(rs[b]) == reset       # a lower level follows
        drop = R - int(rem[b]) if nxt else None
        first = a == 0 or int(rs[a - 1]) != reset
        made = reset - D                           # the bucket's first window
        idx = np.arange(a, b)
        idx = idx[np.lexsort((lo[idx], hi[idx]))]  # by receive time
        l, h = lo[idx].tolist(), np.minimum(hi[idx], reset).tolist()
        if first:
            if status == OVER and L > 1 or R != L - 1:
                return "none", (f"the bucket reset={reset} starts at "
                                f"remaining={R}, not at {L - 1}")
            if made < t_prev:
                return ("none" if exact else "undecided",
                        f"bucket made at {made}, before the answers of the "
                        f"bucket before it were given")
            if a and made <= int(rs[a - 1]):
                return "none", (f"bucket made at {made}, while the bucket "
                                f"before it was still live")
            fits = [k for k in range(len(idx)) if l[k] <= made <= h[k]]
            size = b - a
            whole = (len(fits) == size
                     and (not nxt or int(rem[b]) == max(L - size, 0)))
            if whole:
                windows.append((idx, made))
                t_prev = made
                continue
            if not fits:
                return "none", (f"bucket made at {made}, outside the span of "
                                f"every request that was answered from it")
            # one request made the bucket; the others read remaining = L - 1
            k = fits[0]
            windows.append((idx[k:k + 1], made))
            t_prev = made
            idx = np.delete(idx, k)
            del l[k], h[k]
            if not len(idx):
                if nxt:
                    return "none", (f"one request made the bucket, the next "
                                    f"answers say remaining={int(rem[b])}")
                continue
        size = len(idx)
        if R == 0:
            # an empty bucket refuses every window: each answer its own
            for k in range(size):
                t = max(t_prev, l[k])
                if t > h[k]:
                    return ("none" if exact else "undecided",
                            f"no timestamp left for an OVER_LIMIT answer of "
                            f"reset={reset}: needs >= {t}, answered by {h[k]}")
                windows.append((idx[k:k + 1], t))
                t_prev = t
            continue
        if drop is not None:
            if drop <= 0:
                return "none", f"remaining rises from {R} within one bucket"
            refused = size - drop
            if refused < 0 or 0 < refused <= R:
                return "none", (
                    f"{size} answers say remaining={R} and the next say "
                    f"{R - drop}: the window's {drop} hits do not add up")
        if size <= R:
            # too few for a refused window: they are one window
            t = max(t_prev, max(l))
            if t > min(h):
                return ("none" if exact else "undecided",
                        f"the {size} requests that read remaining={R} share "
                        f"no timestamp, and a window of them lands its hits")
            windows.append((idx, t))
            t_prev = t
            continue
        runs = _runs(l, h, t_prev, R, drop, steps)
        if steps[0] > budget:
            return "undecided", "search budget spent"
        if runs is None:
            return "undecided", (f"no split of the {size} answers at "
                                 f"remaining={R} into refused windows found")
        exact = False
        for j, i, t in runs:
            windows.append((idx[j:i], t))
        t_prev = runs[-1][2] if runs else t_prev
    # replay the witness through the rule, window by window
    row = None
    for members, t in windows:
        row, got = rule(row.copy() if row is not None else None,
                        [(1, L, D, TOKEN)] * len(members), int(t))
        for p, g in zip(members.tolist(), got):
            want = (int(st[p]), L, int(rem[p]), int(rs[p]))
            if g != want:
                return "undecided", (f"served {want}, the rule says {g} for a "
                                     f"window of {len(members)} at {int(t)}")
    return "ok", None


def _global_leaky_key(ops, hint, L, D, rule, guide):
    """Witness for one leaky key of the GLOBAL family (module docstring):
    found and confirmed, or undecided."""
    s, e, st, rem, rs = ops
    rate = max(D // max(L, 1), 1)
    if np.any((st == OVER) & (rem != 0)):
        return "none", "refused (hits = 1) yet tokens left"
    lo, hi = _spans(s, e)
    over = st == OVER
    told = np.where(over, rs - rate, 0)
    if np.any(over & ((told < lo) | (told > hi))):
        return "none", ("an OVER_LIMIT answer names a timestamp outside its "
                        "request's span")
    ok_hint = (hint > 0) & (hint >= lo) & (hint <= hi)
    h = np.where(over, told,
                 np.where(ok_hint, hint, _guessed(e, lo, hi, guide)))
    lo = np.where(over, told, lo)
    hi = np.where(over, told, hi)
    order = np.lexsort((e, -rem, over, h)).tolist()
    cols = (lo.tolist(), hi.tolist(), h.tolist(), st.tolist(), rem.tolist(),
            rs.tolist())
    why = None
    # two ways to cut the order into windows: equal answers that follow each
    # other are one window; or only where their guessed timestamp is one too
    for same_guess in (False, True):
        why = _leaky_windows(order, cols, same_guess, L, D, rate, rule)
        if why is None:
            return "ok", None
    return "undecided", why


def _leaky_windows(order, cols, same_guess, L, D, rate, rule):
    """Cut `order` into windows, find each window's timestamp, replay.
    None if the rule confirms the witness, else what stood in the way."""
    lo, hi, h, st, rem, rs = cols
    wins = []                      # [positions, lo, hi]
    for p in order:
        w = wins[-1] if wins else None
        q = w[0][0] if w else None
        if (w is not None and st[q] == st[p] and rem[q] == rem[p]
                and rs[q] == rs[p] and max(w[1], lo[p]) <= min(w[2], hi[p])
                and (not same_guess or h[q] == h[p])):
            w[0].append(p)
            w[1], w[2] = max(w[1], lo[p]), min(w[2], hi[p])
        else:
            wins.append([[p], lo[p], hi[p]])
    # each window's timestamp: forward as an interval, then last to first
    R, flo, fhi = None, -INF, INF
    fwd = []
    for members, a, b in wins:
        q, m = members[0], len(members)
        left = rem[q]
        if R is None:
            gap = (0, INF)
            if st[q] == OVER or left != L - 1:
                return "the first answers are not a new bucket's"
            after = max(L - m, 0)
        else:
            if st[q] == OVER:
                if R != 0:
                    return "OVER_LIMIT with tokens left before it"
                gap = (0, rate - 1)
            elif left < L:
                gap = ((left - R) * rate, (left - R) * rate + rate - 1)
            else:
                gap = ((L - R) * rate, INF)
            after = left - m if m <= left else left
            if (st[q] != OVER and left == L - 1
                    and (left < R or max(a, flo + gap[0]) > min(b, fhi + gap[1]))):
                # not what has leaked back: the row expired, a new bucket
                # (the replay holds the expiry to the rule)
                gap, after = (0, INF), max(L - m, 0)
            elif left < R:
                return f"remaining={left} read after a window that left {R}"
        a, b = max(a, flo + gap[0]), min(b, fhi + gap[1])
        if a > b:
            return "no timestamps found for the guessed windows"
        fwd.append((a, b, gap))
        R, flo, fhi = after, a, b
    nows, nxt, gap = [0] * len(wins), None, None
    for i in range(len(wins) - 1, -1, -1):
        a, b, mygap = fwd[i]
        if nxt is not None:
            if gap[1] != INF:
                a = max(a, nxt - gap[1])
            b = min(b, nxt - gap[0])
        if a > b:
            return "the witness's intervals do not close"
        nows[i] = nxt = int(b)
        gap = mygap
    row = None
    for (members, _, _), t in zip(wins, nows):
        row, got = rule(row.copy() if row is not None else None,
                        [(1, L, D, LEAKY)] * len(members), t)
        for p, g in zip(members, got):
            want = (st[p], L, rem[p], rs[p])
            if g != want:
                return (f"served {want}, the rule says {g} for a window of "
                        f"{len(members)} at {t}")
    return None


def told_lag(ops, keyspace, window):
    """How far the server's clock lies behind the clients': median of
    (received - told timestamp) over the answers that tell theirs (a leaky
    OVER_LIMIT answer: reset - rate; a token answer that made its bucket:
    reset - duration), in the first and in the last fifth of `window`
    (epoch seconds).  None where nothing told."""
    rank, recv = ops["rank"], ops["recv"]
    if not len(rank):
        return {"first_fifth_ms": None, "last_fifth_ms": None, "told": 0}
    over, at = _leaky_told(ops, keyspace)
    made = ((keyspace.algos_of(rank) == TOKEN) & (ops["status"] != OVER)
            & (ops["remaining"] == keyspace.limits_of(rank) - 1)
            & (rank <= keyspace.population))
    now = np.where(over, at, ops["reset"] - keyspace.durations_of(rank))
    w0, w1 = window[0] * 1e3, window[1] * 1e3
    fifth = (w1 - w0) / 5.0
    out = {"told": int((over | made).sum())}
    for name, a, b in (("first_fifth_ms", w0, w0 + fifth),
                       ("last_fifth_ms", w1 - fifth, w1)):
        m = (over | made) & (recv >= a) & (recv < b)
        out[name] = float(np.median(recv[m] - now[m])) if m.any() else None
    return out


def check(ops, tainted, keyspace, apply, max_report=5, global_window=None):
    """ops: dict of equal-length arrays rank, sent, recv (epoch ms), status,
    remaining, reset, hint.  Returns the numbers compared and some words on
    the first keys that failed.  Keys of the `global` family are compared
    under `global_window` (module docstring), the others under `apply`; the
    `global_*` numbers count that family's share of the totals."""
    rank = ops["rank"]
    out = {"followed_decisions": int(len(rank)), "checked_decisions": 0,
           "checked_keys": 0, "mismatched_keys": 0, "undecided_decisions": 0,
           "undecided_keys": 0, "tainted_keys": 0, "reports": []}
    if keyspace.glob is not None:
        out.update(global_followed_decisions=int(
            (rank > keyspace.population).sum()), global_checked_decisions=0,
            global_checked_keys=0, global_mismatched_keys=0,
            global_undecided_decisions=0, global_undecided_keys=0)
    if not len(rank):
        return out
    bad = set(int(x) for x in np.unique(tainted))
    order = np.argsort(rank, kind="stable")
    r_sorted = rank[order]
    cuts = np.flatnonzero(np.diff(r_sorted)) + 1
    guide = _guide(ops, keyspace)
    for idx in np.split(order, cuts):
        rk = int(rank[idx[0]])
        if rk in bad:
            out["tainted_keys"] += 1
            continue
        L, algo, D = keyspace.limit(rk), keyspace.algo(rk), keyspace.duration(rk)
        glob = keyspace.is_global(rk)
        cols = (ops["sent"][idx], ops["recv"][idx],
                ops["status"][idx].astype(np.int64),
                ops["remaining"][idx], ops["reset"][idx])
        if glob and global_window is None:
            raise ValueError("a key of the `global` family, and no "
                             "`global_window` rule to compare it under")
        if glob and algo == TOKEN:
            verdict, why = _global_token_key(cols, L, D, global_window,
                                             400 * len(idx) + 4000)
        elif glob:
            verdict, why = _global_leaky_key(cols, ops["hint"][idx], L, D,
                                             global_window, guide)
        elif algo == TOKEN:
            why = _token_key(cols, L, D, apply)
            verdict = "none" if why else "ok"
        else:
            verdict, why = _leaky_key(cols, ops["hint"][idx], L, D, apply,
                                      100 * len(idx) + 4000, guide)
        for pre in ("", "global_") if glob else ("",):
            if verdict == "ok":
                out[pre + "checked_keys"] += 1
                out[pre + "checked_decisions"] += len(idx)
            elif verdict == "none":
                out[pre + "mismatched_keys"] += 1
            else:
                out[pre + "undecided_decisions"] += len(idx)
                out[pre + "undecided_keys"] += 1
        if why and len(out["reports"]) < max_report:
            out["reports"].append(
                f"rank {rk} ({'GLOBAL ' if glob else ''}"
                f"{'leaky' if algo else 'token'}, limit {L}, "
                f"{len(idx)} answers): {verdict}: {why}")
    return out
