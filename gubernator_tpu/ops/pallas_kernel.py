"""Pallas TPU kernels for the rate-limit hot passes.

Three lowerings, chosen by what actually profits from hand-scheduling on TPU
(everything here is gated behind env flags; the engine defaults to the XLA
implementations, which are semantically identical):

1. `global_apply_pallas` (GUBER_PALLAS=1) — the GLOBAL aggregate-apply: a
   pure elementwise transition over the whole replicated arena,
   grid-blocked through VMEM.

2. `window_step_pallas` (GUBER_PALLAS=1) — the per-shard serving window.
   The WINDOW MATH (closed-form uniform segments + the duplicate-key replay
   rounds) runs as ONE VMEM-resident kernel over the [B] lane vectors, with
   the replay's register state formulated REPLICATED-per-lane so each round
   is elementwise + one vector gather (no scatters in the kernel).  The
   argsort and the arena gather/scatter stay in XLA.

3. `window_step_fused` (GUBER_PALLAS_FUSED=1) — the FULL compact serving
   window as ONE pallas_call: wire decode, slot sort (in-kernel bitonic),
   segment prep, uniform/replay transitions, the replay-free fold path,
   arena commit (one write per touched slot) and the compact response
   encode all inside a single kernel whose arena planes are aliased
   in/out.  This is the per-kernel-overhead killer: the compact32-XLA
   drain lowers a K-window dispatch to hundreds of executed kernels
   (gathers, scatters, sort passes, elementwise stages — each a launch),
   where the
   fused form executes O(1) kernels per window.  Everything runs in
   rebased int32 (arena i64 timestamps enter as (lo, hi) half planes and
   are rebased with explicit borrow/carry pair arithmetic), which is the
   only form Mosaic accepts on real TPU — no 64-bit vector types.

All kernel bodies *reuse* `kernel.transition` / `kernel.uniform_closed_form`
/ `_window_math` / `kernel.segment_structure` — the exact branch ladders
that mirror reference algorithms.go:24-186 — so the Pallas and XLA paths
cannot drift semantically, and the fuzz oracle (tests/pyref.py) plus the
int64 kernel (ops/kernel.py, kept as the bit-exact oracle) pin all of them.

State is int64 (ms-epoch timestamps + proto-contract counters).  The chip's
compiler refuses every Pallas drain lowering today (tests/test_tpu_compile.py
holds its words; engine._MOSAIC_REFUSED makes their flags an error on a TPU
mesh), so the XLA path is the default and the only one a TPU runs; CPU tests
run the kernels in interpret mode and pin them against the XLA
implementation.
"""

from __future__ import annotations

import contextlib
import functools
import sys
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from gubernator_tpu.ops import kernel
from gubernator_tpu.ops.kernel import (
    BucketState,
    GlobalConfig,
    WindowBatch,
    WindowOutput,
    _Reg,
    I32,
    I64,
)

# lanes per grid step; arenas are sized in powers of two >= 1024
BLOCK = 1024


def _vma(x):
    """The mesh axes `x` varies over (empty outside shard_map or with
    check_vma off); pallas_call outputs must carry their operands' tag."""
    return jax.typeof(x).vma


def fused_enabled(default: bool = False) -> bool:
    """Shared GUBER_PALLAS_FUSED reader (config.env_bool normalization:
    0/1/true/false/yes/no/on/off, warn on anything else).  The engine's
    compiled-builder cache keys, the bench probes, and tests must all
    normalize this flag identically — a reader that only accepted the
    literal "1" silently disabled the megakernel on `=true`."""
    from gubernator_tpu.config import env_bool
    return env_bool("GUBER_PALLAS_FUSED", default)


def kernel_census(closed) -> int:
    """Executed-kernel proxy over a ClosedJaxpr: count equations, recursing
    into sub-jaxprs (scan/while/cond/pjit bodies count once — per-window
    cost), with a pallas_call counting as ONE kernel regardless of its
    body.  On real TPU each surviving top-level op is at least one kernel
    launch (XLA fusion only merges elementwise neighbors; the gathers,
    scatters, sort passes and the scan skeleton stay distinct), so census
    ratios are a conservative stand-in for launch-count ratios.  Shared by
    the fused-megakernel test suites and bench.py's per-arm census.

    A `lax.platform_dependent` switch (kernel.floordiv) counts as its
    default branch alone: one branch survives lowering, and the census is
    taken from a CPU trace.  What the TPU branch costs is a chip
    measurement, not a count."""
    def walk(jaxpr):
        n = 0
        platform_idx = set()
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
                continue
            if eqn.primitive.name == "platform_index":
                platform_idx.update(eqn.outvars)
                continue
            if eqn.primitive.name == "cond" and eqn.invars[0] in platform_idx:
                n += walk(eqn.params["branches"][-1].jaxpr)
                continue
            subs = []
            for v in eqn.params.values():
                vs = v if isinstance(v, (tuple, list)) else (v,)
                for x in vs:
                    if hasattr(x, "jaxpr"):
                        subs.append(x.jaxpr)   # ClosedJaxpr
                    elif hasattr(x, "eqns"):
                        subs.append(x)         # Jaxpr
            n += sum(walk(s) for s in subs) if subs else 1
        return n
    return walk(closed.jaxpr)


@contextlib.contextmanager
def mosaic_recursion_guard(limit: int = 20000):
    """Temporarily raise the recursion ceiling around a Mosaic lowering.

    Lowering the fused window-math jaxpr (closed-form ladder + replay loop
    as ONE Mosaic kernel) recurses past CPython's default 1000 frames
    inside jax's mlir lowering on real TPU (observed: RecursionError during
    the OUTER jit's compile, at first call of the compiled step — interpret
    mode on CPU stays shallower and never trips it).  The lowering runs at
    the first CALL of the engine's compiled executables, so the engine
    wraps those call sites in this guard (core/engine.py _recursion_guarded)
    rather than bumping the limit process-globally at import — an import
    side effect would leak a 20x ceiling into every embedding application.
    The jaxpr nesting is finite (a few thousand frames),
    and CPython 3.12 heap-allocates Python-to-Python frames, so the
    temporary ceiling does not threaten the C stack.
    """
    prev = sys.getrecursionlimit()
    if prev < limit:
        sys.setrecursionlimit(limit)
    try:
        yield
    finally:
        sys.setrecursionlimit(prev)


def _apply_kernel(now_ref, limit_ref, dur_ref, rem_ref, ts_ref, exp_ref,
                  algo_ref, cl_ref, cd_ref, ca_ref, sum_ref,
                  o_limit, o_dur, o_rem, o_ts, o_exp, o_algo):
    reg = _Reg(
        limit=limit_ref[:],
        duration=dur_ref[:],
        remaining=rem_ref[:],
        tstamp=ts_ref[:],
        expire=exp_ref[:],
        algo=algo_ref[:],
    )
    now = now_ref[0]
    summed = sum_ref[:]
    cfg_algo = ca_ref[:]
    fresh = (reg.expire < now) | (cfg_algo != reg.algo)
    new_reg, _ = kernel.transition(
        reg, summed, cl_ref[:], cd_ref[:], cfg_algo, now, fresh)
    touched = summed != 0
    o_limit[:] = jnp.where(touched, new_reg.limit, reg.limit)
    o_dur[:] = jnp.where(touched, new_reg.duration, reg.duration)
    o_rem[:] = jnp.where(touched, new_reg.remaining, reg.remaining)
    o_ts[:] = jnp.where(touched, new_reg.tstamp, reg.tstamp)
    o_exp[:] = jnp.where(touched, new_reg.expire, reg.expire)
    o_algo[:] = jnp.where(touched, new_reg.algo, reg.algo)


@functools.partial(jax.jit, static_argnames=("interpret",))
def global_apply_pallas(state: BucketState, cfg: GlobalConfig,
                        summed_hits: jax.Array, now, *,
                        interpret: bool = False) -> BucketState:
    """Drop-in replacement for kernel.global_apply via pallas_call."""
    G = state.limit.shape[0]
    block = min(BLOCK, G)
    assert G % block == 0, "global arena capacity must be a multiple of the block"
    grid = (G // block,)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    now_arr = jnp.asarray(now, jnp.int64).reshape((1,))

    # the global arena is replicated across the mesh, so under shard_map
    # with check_vma the outputs vary over no axes (vma=()); with check_vma
    # off (the engine's Pallas mode) or outside shard_map, vma is None
    vma = _vma(state.limit)
    sds = lambda dt: jax.ShapeDtypeStruct((G,), dt, vma=vma)
    out_shapes = [sds(jnp.int64)] * 5 + [sds(jnp.int32)]
    outs = pl.pallas_call(
        _apply_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),  # now (broadcast)
            spec, spec, spec, spec, spec, spec,  # state
            spec, spec, spec,                    # cfg
            spec,                                # summed
        ],
        out_specs=[spec] * 6,
        out_shape=out_shapes,
        interpret=interpret,
    )(now_arr, state.limit, state.duration, state.remaining, state.tstamp,
      state.expire, state.algo, cfg.limit, cfg.duration, cfg.algo, summed_hits)
    return BucketState(*outs)


# ---- the serving window kernel ------------------------------------------


# The one window-math body — the generalized zero-replay fold plus the
# residual replay loop — lives in ops/kernel.py (window_math) so the
# int64 oracle, the compact32 XLA path, the per-window Pallas kernel and
# the fused megakernel all run literally the same function.
_window_math = kernel.window_math


def _window_math_kernel(now_ref, maxpos_ref,
                        s_valid, s_hits, s_limit, s_duration, s_algo,
                        s_init, s_agg, pos, seg_len, seg_start_idx,
                        seg_fold, h0, l0, d0, a0, fresh_seg, nz, n_lead,
                        hstar,
                        r_lim, r_dur, r_rem, r_ts, r_exp, r_algo,
                        o_status, o_limit, o_rem, o_reset,
                        f_lim, f_dur, f_rem, f_ts, f_exp, f_algo):
    """Pallas Ref wrapper around _window_math (reads refs, writes refs)."""
    reg = _Reg(limit=r_lim[:], duration=r_dur[:], remaining=r_rem[:],
               tstamp=r_ts[:], expire=r_exp[:], algo=r_algo[:])
    out_sorted, fin = _window_math(
        now_ref[0], maxpos_ref[0], s_valid[:], s_hits[:], s_limit[:],
        s_duration[:], s_algo[:], s_agg[:], pos[:], seg_len[:],
        seg_start_idx[:], seg_fold[:], h0[:], l0[:], d0[:], a0[:],
        fresh_seg[:], reg, nz[:], n_lead[:], hstar[:])
    o_status[:] = out_sorted.status
    o_limit[:] = out_sorted.limit
    o_rem[:] = out_sorted.remaining
    o_reset[:] = out_sorted.reset_time
    f_lim[:] = fin.limit
    f_dur[:] = fin.duration
    f_rem[:] = fin.remaining
    f_ts[:] = fin.tstamp
    f_exp[:] = fin.expire
    f_algo[:] = fin.algo


@functools.partial(jax.jit,
                   static_argnames=("interpret", "compact32", "use_pallas"))
def window_step_pallas(state, batch: WindowBatch, now, *,
                       interpret: bool = False, compact32: bool = False,
                       use_pallas: bool = True):
    """Drop-in replacement for kernel.window_step with the window math in
    one Pallas kernel.  Sort, segment indexing, the arena gather, and the
    final scatter/unsort stay in XLA (see the module docstring for why).

    use_pallas=False runs the IDENTICAL math (_window_math, same rebase
    and re-absolutize) as plain traced XLA — with compact32=True that is
    the engine's default serving form (window_step_compact32_xla below):
    int64 arithmetic on TPU lowers to multi-op i32-pair emulation, so
    running the ladder in rebased int32 roughly halves the math's op
    count even without Mosaic.

    compact32=True runs the kernel body entirely in int32 with times
    REBASED to the window's `now` — Mosaic on real TPU has no 64-bit
    vector types (round-4 probe: "64-bit types are not supported"), and
    this is what makes the Pallas path runnable on hardware.  It is exact
    iff every lane satisfies the compact wire-format ranges
    (kernel.COMPACT_MAX_*: hits < 2^28, limit < 2^31, duration < 2^31-16)
    AND the arena rows it reads were written under the same caps — both
    guaranteed on the engine's compact serving path (the engine
    permanently drops to the full-format XLA path the first time an
    out-of-range config appears, core/engine.py _dispatch).  Rebased
    time identities: every absolute time the ladder computes is now+X
    with X in (-2^31, 2^31); non-fresh registers satisfy
    |t - now| <= max request duration < 2^31-16 (token: tstamp = expire
    >= now and <= write_now+duration; leaky: expire = last-decrement
    now+duration >= now) PROVIDED the window clock is monotonic — the
    engine's serving clocks are.  A clock that jumps backward by D ms
    can push a stored time up to D past the rebase range; the clip then
    bounds the resulting expiry error to D (graceful, not wrong-branch)."""
    B = batch.slot.shape[0]
    now = jnp.asarray(now, dtype=I64)

    # identical sort/segment/uniform prep as the XLA path — shared code, so
    # the two implementations cannot drift
    prep = kernel.window_prep(state, batch, now)
    (_, _, s_valid, s_hits, s_limit, s_duration, s_algo, s_init,
     _, seg_start_idx, pos, seg_len, cur, fresh_seg, h0, l0, d0, a0,
     nz, n_lead, hstar, seg_fold, max_pos, _commit_mask, s_agg) = prep

    if compact32:
        lim = jnp.int64(2**31 - 16)
        rel = lambda t: jnp.clip(t - now, -lim, lim).astype(I32)
        cnt = lambda x: x.astype(I32)
        k_hits, k_limit, k_dur = cnt(s_hits), cnt(s_limit), cnt(s_duration)
        k_h0, k_l0, k_d0 = cnt(h0), cnt(l0), cnt(d0)
        k_hstar = cnt(hstar)
        k_cur = _Reg(limit=cnt(cur.limit), duration=cnt(cur.duration),
                     remaining=cnt(cur.remaining), tstamp=rel(cur.tstamp),
                     expire=rel(cur.expire), algo=cur.algo)
        k_now = jnp.zeros((1,), I32)
        VD = I32
    else:
        k_hits, k_limit, k_dur = s_hits, s_limit, s_duration
        k_h0, k_l0, k_d0 = h0, l0, d0
        k_hstar = hstar
        k_cur = cur
        k_now = now.reshape((1,))
        VD = I64

    # under shard_map with check_vma the window arrays vary over the shard
    # axis; mirror the input's vma on the outputs.  The engine disables
    # check_vma on its shard_maps when Pallas is enabled (vma tags do not
    # survive the kernel's interpret-mode while_loop), in which case typeof
    # has no vma and None is correct.
    if use_pallas:
        vma = _vma(batch.slot)
        sds = lambda dt: jax.ShapeDtypeStruct((B,), dt, vma=vma)
        spec = pl.BlockSpec((B,), lambda: (0,))
        sspec = pl.BlockSpec((1,), lambda: (0,))
        outs = pl.pallas_call(
            _window_math_kernel,
            in_specs=[sspec, sspec] + [spec] * 25,
            out_specs=[spec] * 10,
            out_shape=[sds(I32), sds(VD), sds(VD), sds(VD),   # outputs
                       sds(VD), sds(VD), sds(VD), sds(VD), sds(VD),
                       sds(I32)],                             # final regs
            interpret=interpret,
        )(k_now, max_pos.reshape((1,)),
          s_valid, k_hits, k_limit, k_dur, s_algo, s_init, s_agg,
          pos, seg_len, seg_start_idx, seg_fold,
          k_h0, k_l0, k_d0, a0, fresh_seg, nz, n_lead, k_hstar,
          k_cur.limit, k_cur.duration, k_cur.remaining, k_cur.tstamp,
          k_cur.expire, k_cur.algo)
        out_sorted = WindowOutput(status=outs[0], limit=outs[1],
                                  remaining=outs[2], reset_time=outs[3])
        fin = _Reg(limit=outs[4], duration=outs[5], remaining=outs[6],
                   tstamp=outs[7], expire=outs[8], algo=outs[9])
    else:
        out_sorted, fin = _window_math(
            k_now[0], max_pos, s_valid, k_hits, k_limit, k_dur, s_algo,
            s_agg, pos, seg_len, seg_start_idx, seg_fold,
            k_h0, k_l0, k_d0, a0, fresh_seg, k_cur, nz, n_lead, k_hstar)
    if compact32:
        # re-absolutize.  reset_time: leaky and concurrency use 0 as the
        # "no reset" sentinel (leaky's non-zero resets are now+rate with
        # rate >= 1; concurrency resets are ALWAYS the sentinel), so
        # rel == 0 distinguishes exactly; token/GCRA/sliding lanes always
        # carry a real time (rel 0 == "resets at now") and never the
        # sentinel (algorithms.go:130-141 vs :69-74).
        leaky_lane = ((s_algo == kernel.LEAKY_BUCKET)
                      | (s_algo == kernel.CONCURRENCY))
        reset64 = jnp.where(
            leaky_lane & (out_sorted.reset_time == 0), jnp.int64(0),
            out_sorted.reset_time.astype(I64) + now)
        out_sorted = WindowOutput(
            status=out_sorted.status, limit=out_sorted.limit.astype(I64),
            remaining=out_sorted.remaining.astype(I64), reset_time=reset64)
        fin = _Reg(limit=fin.limit.astype(I64),
                   duration=fin.duration.astype(I64),
                   remaining=fin.remaining.astype(I64),
                   tstamp=fin.tstamp.astype(I64) + now,
                   expire=fin.expire.astype(I64) + now,
                   algo=fin.algo)
    return kernel.window_commit(state, prep, fin, out_sorted)


def window_step_compact32_xla(state, batch: WindowBatch, now):
    """The serving drain's default window step: the rebased-int32 math as
    plain traced XLA (no Mosaic dependency).  Exact under the compact
    wire-format range caps — the only context the engine calls it in
    (see window_step_pallas's compact32 notes for the rebase identities).
    """
    return window_step_pallas(state, batch, now, compact32=True,
                              use_pallas=False)


# ---- the fused serving-window megakernel --------------------------------

_REBASE_LIM = 2**31 - 16


def _u32(x):
    return lax.bitcast_convert_type(x, jnp.uint32)


def _pair_rebase(t_lo, t_hi, n_lo, n_hi):
    """clip(t - now, -REBASE_LIM, REBASE_LIM) on (lo, hi) i32 halves.

    Exact vs the int64 form for every input: the borrow subtract yields the
    wrapped i64 difference's halves; when it fits int32 the clip sees the
    true difference, otherwise the hi half's sign picks the saturation end
    — identical to clipping the i64 value (verified over random i64s in
    tests/test_fused_megakernel.py)."""
    d_lo = t_lo - n_lo
    borrow = (_u32(t_lo) < _u32(n_lo)).astype(I32)
    d_hi = t_hi - n_hi - borrow
    fits = d_hi == (d_lo >> 31)
    lim = jnp.int32(_REBASE_LIM)
    return jnp.where(fits, jnp.clip(d_lo, -lim, lim),
                     jnp.where(d_hi < 0, -lim, lim))


def _pair_reabs(rel, n_lo, n_hi):
    """now + rel on (lo, hi) i32 halves (exact i64 add: sign-extended rel,
    carry from unsigned lo overflow)."""
    a_lo = n_lo + rel
    carry = (_u32(a_lo) < _u32(rel)).astype(I32)
    a_hi = n_hi + (rel >> 31) + carry
    return a_lo, a_hi


# ---- general (lo, hi) i32-pair arithmetic ---------------------------------
#
# The rebase/reabs helpers above only cover times within +/-2^31 of `now`.
# The GLOBAL ladder has no such contract (its stored state is exempt from
# the compact caps), so its Mosaic form runs FULL i64 arithmetic as exact
# two's-complement pair ops: lo halves add/subtract as u32 with explicit
# carry/borrow, hi halves carry the sign.  Every op below is the bit-exact
# image of the corresponding i64 op (wrap included), so a ladder built from
# them cannot diverge from the int64 oracle even on adversarial inputs.

# the zero pair as plain Python ints: weak-typed literals inline into any
# kernel trace (a module-level jnp scalar would be a captured constant,
# which pallas_call kernels reject)
_P0 = (0, 0)


def _p_add(a, b):
    lo = a[0] + b[0]
    carry = (_u32(lo) < _u32(a[0])).astype(I32)
    return lo, a[1] + b[1] + carry


def _p_sub(a, b):
    borrow = (_u32(a[0]) < _u32(b[0])).astype(I32)
    return a[0] - b[0], a[1] - b[1] - borrow


def _p_lt(a, b):
    """Signed a < b."""
    return (a[1] < b[1]) | ((a[1] == b[1]) & (_u32(a[0]) < _u32(b[0])))


def _p_eq(a, b):
    return (a[0] == b[0]) & (a[1] == b[1])


def _p_is0(a):
    return (a[0] | a[1]) == 0


def _p_where(c, a, b):
    return jnp.where(c, a[0], b[0]), jnp.where(c, a[1], b[1])


def _p_min(a, b):
    return _p_where(_p_lt(a, b), a, b)


def _p_chain(pairs, default):
    """kernel._chain for pair values: first-match-wins where-fold."""
    out = default
    for cond, val in reversed(pairs):
        out = _p_where(cond, val, out)
    return out


def _p_sext(v):
    """i32 value -> its exact i64 image as a (lo, hi) pair."""
    return v, v >> 31


def _shr_u(x, s):
    """Logical (zero-fill) right shift on i32, via the u32 view — jnp
    right_shift on int32 is arithmetic, and the lax logical shift does
    not broadcast a scalar count."""
    return lax.bitcast_convert_type(_u32(x) >> s, I32)


def _p_shr(p, d):
    """Arithmetic right shift of an i64 pair by a traced scalar d in
    [0, 63] — the sketch decay (`sketch >> decay`; the engine passes the
    0/1 halving flag, but the oracle semantics hold for the whole range).
    Shift counts of 0 and >=32 are special-cased: XLA shifts are
    undefined at the word width, so the three ranges select explicitly."""
    lo, hi = p
    d = jnp.clip(d, 0, 63)
    sa = jnp.clip(d, 1, 31)                 # in-word case: d in [1, 31]
    lo_a = _shr_u(lo, sa.astype(jnp.uint32)) | (hi << (32 - sa))
    hi_a = hi >> sa
    sb = jnp.clip(d - 32, 0, 31)            # cross-word case: d in [32, 63]
    lo_b, hi_b = hi >> sb, hi >> 31
    big = d >= 32
    lo_s = jnp.where(big, lo_b, lo_a)
    hi_s = jnp.where(big, hi_b, hi_a)
    return _p_where(d == 0, p, (lo_s, hi_s))


# 14-bit limb decomposition of a pair: l4..l0 are the literal bit fields
# (14, 14, 14, 14, 8 bits), so sum(l_j << 14j) mod 2^64 reconstructs the
# value exactly — two's complement included.  Limbs let per-bucket i64
# totals accumulate through i32 lane sums (each partial < lanes * 2^14)
# without a 64-bit vector ALU.
def _p_limbs(p):
    lo, hi = p
    M = 0x3FFF
    return (lo & M,
            _shr_u(lo, 14) & M,
            (_shr_u(lo, 28) | (hi << 4)) & M,
            _shr_u(hi, 10) & M,
            _shr_u(hi, 24) & 0xFF)


def _p_from_limbs(c0, c1, c2, c3, c4):
    """Rebuild the pair from (possibly carried-into) non-negative i32 limb
    sums: value = sum(c_j * 2^(14 j)) mod 2^64.  Exact for any c_j in
    [0, 2^31): the shifted partials are each exact u64 images and pair
    addition wraps like i64."""
    z = jnp.zeros_like(c0)
    p = (c0, z)
    p = _p_add(p, (c1 << 14, _shr_u(c1, 18)))
    p = _p_add(p, (c2 << 28, _shr_u(c2, 4)))
    p = _p_add(p, (z, c3 << 10))
    return _p_add(p, (z, c4 << 24))


def _bitonic_sort_by_slot(sort_key):
    """(sorted_key, order) for a power-of-two lane vector — the in-kernel
    equivalent of `jnp.argsort(sort_key)` + gather.

    Lexicographic (key, lane) comparisons make the network STABLE despite
    bitonic networks not being: the lane index breaks every tie in arrival
    order, which the replay semantics require (duplicate hits to one slot
    must apply in arrival order).  XOR-partner exchanges are two vector
    gathers + elementwise selects per stage, log2(B)·(log2(B)+1)/2 stages,
    all Mosaic-legal — no sort primitive needed."""
    B = sort_key.shape[0]
    lane = lax.iota(I32, B)
    key, idx = sort_key, lane
    k = 2
    while k <= B:
        j = k // 2
        while j >= 1:
            partner = lane ^ j
            p_key = jnp.take(key, partner)
            p_idx = jnp.take(idx, partner)
            ascending = (lane & k) == 0
            less = (key < p_key) | ((key == p_key) & (idx < p_idx))
            is_lower = (lane & j) == 0
            keep = jnp.where(is_lower, less == ascending, less != ascending)
            key = jnp.where(keep, key, p_key)
            idx = jnp.where(keep, idx, p_idx)
            j //= 2
        k *= 2
    return key, idx


class FusedState32(NamedTuple):
    """The bucket arena as i32 planes — the form the fused megakernel
    reads/writes in place (aliased pallas_call operands).

    limit/duration/remaining are plain truncations: the compact serving
    path guarantees their stored values are inside the compact caps
    (< 2^31, engine._compact_eligible), so the low half IS the value.
    tstamp/expire are ms-epoch int64s that do NOT fit 32 bits; they travel
    as exact (lo, hi) bitcast halves and only ever get rebased/committed
    through the pair helpers above.  The pipeline drain converts once per
    K-window dispatch and carries THIS form through the scan, so the O(C)
    plane conversion is amortized over the whole drain."""

    limit: jax.Array      # i32[C]
    duration: jax.Array   # i32[C]
    remaining: jax.Array  # i32[C]
    t_lo: jax.Array       # i32[C]
    t_hi: jax.Array       # i32[C]
    e_lo: jax.Array       # i32[C]
    e_hi: jax.Array       # i32[C]
    algo: jax.Array       # i32[C]


def fused_state_to_planes(state) -> FusedState32:
    """The resident planes (kernel.ArenaPlanes; int64 rows are split first)
    as the megakernel's i32 planes: bitcasts of the halves it reads, the
    high halves of limit/duration/remaining left behind (zero under the
    compact caps)."""
    a = (kernel.arena_from_rows(state) if isinstance(state, BucketState)
         else state)
    i32 = lambda p: lax.bitcast_convert_type(p, I32)
    return FusedState32(
        limit=i32(a.limit_lo), duration=i32(a.duration_lo),
        remaining=i32(a.remaining_lo),
        t_lo=i32(a.tstamp_lo), t_hi=i32(a.tstamp_hi),
        e_lo=i32(a.expire_lo), e_hi=i32(a.expire_hi),
        algo=a.algo)


def fused_state_from_planes(st32: FusedState32, like=None):
    """Inverse of fused_state_to_planes, into the form the state `like`
    has: the resident planes, or (the default) int64 rows.  The counters'
    high halves are their sign extension, as an i32 -> i64 widening
    gives."""
    u32 = lambda p: lax.bitcast_convert_type(p, jnp.uint32)
    sext = lambda p: u32(p >> 31)
    arena = kernel.ArenaPlanes(
        u32(st32.limit), sext(st32.limit),
        u32(st32.duration), sext(st32.duration),
        u32(st32.remaining), sext(st32.remaining),
        u32(st32.t_lo), u32(st32.t_hi), u32(st32.e_lo), u32(st32.e_hi),
        st32.algo)
    if isinstance(like, kernel.ArenaPlanes):
        return arena
    return kernel.arena_to_rows(arena)


class _FusedAux(NamedTuple):
    """Sorted-domain facts one fused window leaves behind for the in-kernel
    analytics accumulator (_accumulate_window_stats): everything the stats
    reduction needs is already computed by the window body — re-deriving it
    outside the kernel would resurrect the XLA shoulder the fold removes."""

    order: jax.Array        # i32[B] sort permutation (sorted -> lane)
    g: jax.Array            # i32[B] clipped sorted slot (arena gather index)
    s_slot: jax.Array       # i32[B] sorted clean slot (pads -> 2^31-1)
    s_valid: jax.Array      # bool[B]
    s_hits: jax.Array       # i32[B]
    s_init: jax.Array       # bool[B]
    status: jax.Array       # i32[B] sorted response status (0/1)
    commit_mask: jax.Array  # bool[B] one lane per valid slot
    any_commit: jax.Array   # bool scalar
    safe: jax.Array         # i32 scalar: first committing lane (0 if none)
    tgt: jax.Array          # i32[B] rejoined scatter targets


def _commit_ref(ref, aux_or_tuple, fin_vals, raw_vals):
    """One write per touched slot in race-free rejoin form (see the commit
    notes in _fused_window_body): non-commit lanes duplicate the first
    committing lane's write — same target, same value — because Pallas refs
    have no mode="drop" scatter.  Shared by the arena commit and the stats
    plane accumulation so the two scatters cannot drift."""
    commit_mask, any_commit, safe, tgt = aux_or_tuple
    cand = jnp.where(any_commit, fin_vals, raw_vals)
    ref[tgt] = jnp.where(commit_mask, fin_vals, jnp.take(cand, safe))


def _fused_window_body(n_lo, n_hi, req, arena):
    """The whole compact serving window as one kernel-body function over
    VALUES (decoded i32 word columns) and the 8 arena plane REFS — shared
    verbatim by the single-window kernel (_fused_kernel) and the K-grid
    drain kernel (_make_drain_kernel), so the two lowerings cannot drift.

    Stages (each the i32-halves image of the XLA path's stage, same order):
    decode (kernel.decode_batch) → sort (stable bitonic ≡ jnp.argsort) →
    segment prep (kernel.segment_structure / segment_all — the SAME
    functions window_prep calls) → window math (_window_math — the same
    body the split Pallas/XLA paths run) → commit (kernel.window_commit's
    one-write-per-slot scatter, race-free form) → response word encode
    (kernel.encode_output_word) + unsort.  The arena refs are the OUTPUT
    refs of an aliased pallas_call: aliasing initializes them from the
    inputs, so reading them before the commit reads the current arena —
    and in the K-grid drain the same read picks up the PREVIOUS grid
    step's commit, which is exactly the scan carry it replaces.

    Returns (w_lo, w_hi, rlimit, mism, aux) in REQUEST lane order (the
    in-body scatter unsort), with `mism` the i32 stored-vs-request limit
    mismatch flag and `aux` the sorted-domain facts for in-kernel stats."""
    (o_lim, o_dur, o_rem, o_tlo, o_thi, o_elo, o_ehi, o_algo) = arena
    B = req.shape[0]
    C = o_lim.shape[0]
    w0lo, w0hi, w1lo, w1hi = req[:, 0], req[:, 1], req[:, 2], req[:, 3]

    # ---- decode: kernel.decode_batch, reformulated on i32 halves ----
    # (bit 32 group of the i64 word lands in the hi half's low bits; the
    # hits mask clears the arithmetic-shift sign smear)
    slot_raw = w0lo - 1
    hits_raw = (w0hi >> 2) & jnp.int32(kernel.COMPACT_MAX_HITS - 1)
    limit = w1lo
    duration = w1hi & jnp.int32(0x7FFFFFFF)
    # 3-bit algorithm: i64 bit 33 -> hi bit 1, i64 bits 62..63 -> hi bits
    # 30..31 (the & 3 masks the arithmetic-shift sign smear)
    algo = ((w0hi >> 1) & 1) | (((w0hi >> 30) & 3) << 1)
    # concurrency releases: hits sign-extend from bit 27 (kernel.decode_batch)
    conc = jnp.int32(kernel.CONC_MAX_HITS)
    hits = jnp.where(algo == kernel.CONCURRENCY,
                     (hits_raw ^ conc) - conc, hits_raw)
    is_init = (w0hi & 1) == 1

    # ---- window_prep in sorted, rebased-i32 form ----
    valid = slot_raw >= 0
    agg = valid & ((slot_raw & jnp.int32(kernel.AGG_SLOT_BIT)) != 0)
    slot_clean = jnp.where(agg, slot_raw & jnp.int32(~kernel.AGG_SLOT_BIT),
                           slot_raw)
    sort_key = jnp.where(valid, slot_clean, jnp.int32(2**31 - 1))
    s_slot, order = _bitonic_sort_by_slot(sort_key)
    s_valid = jnp.take(valid, order)
    s_hits = jnp.take(hits, order)
    s_limit = jnp.take(limit, order)
    s_duration = jnp.take(duration, order)
    s_algo = jnp.take(algo, order)
    s_init = jnp.take(is_init, order)
    s_agg = jnp.take(agg, order)

    seg_start, seg_start_idx, pos, seg_len, commit_mask = (
        kernel.segment_structure(s_slot, s_valid, s_init))

    g = jnp.clip(s_slot, 0, C - 1)
    raw_lim = o_lim[g]
    raw_dur = o_dur[g]
    raw_rem = o_rem[g]
    raw_tlo = o_tlo[g]
    raw_thi = o_thi[g]
    raw_elo = o_elo[g]
    raw_ehi = o_ehi[g]
    raw_algo = o_algo[g]
    cur = _Reg(limit=raw_lim, duration=raw_dur, remaining=raw_rem,
               tstamp=_pair_rebase(raw_tlo, raw_thi, n_lo, n_hi),
               expire=_pair_rebase(raw_elo, raw_ehi, n_lo, n_hi),
               algo=raw_algo)
    # rebased image of prep's `s_init | (cur.expire < now)`: the clip
    # preserves the difference's sign, so rel < 0 ⇔ expire < now
    cur_fresh = s_init | (cur.expire < 0)

    h0 = jnp.take(s_hits, seg_start_idx)
    l0 = jnp.take(s_limit, seg_start_idx)
    d0 = jnp.take(s_duration, seg_start_idx)
    a0 = jnp.take(s_algo, seg_start_idx)
    fresh_seg = jnp.take(cur_fresh, seg_start_idx)
    # fold classification in the rebased-i32 domain (cur is already
    # rebased to now=0, so fold_classify's leak math matches the split
    # paths' int64 classification under the compact caps)
    seg_fold, nz, n_lead, hstar = kernel.fold_classify(
        s_hits, s_limit, s_duration, s_algo, s_agg, seg_start_idx,
        seg_len, h0, l0, d0, a0, fresh_seg, cur, jnp.int32(0))
    seg_single = s_valid & ~seg_fold & (seg_len == 1)
    max_pos = jnp.max(jnp.where(s_valid & ~seg_fold & ~seg_single, pos,
                                jnp.int32(-1)))

    # ---- the window math: the SAME body as the split paths ----
    out_sorted, fin = _window_math(
        jnp.int32(0), max_pos, s_valid, s_hits, s_limit, s_duration,
        s_algo, s_agg, pos, seg_len, seg_start_idx, seg_fold,
        h0, l0, d0, a0, fresh_seg, cur, nz, n_lead, hstar)

    # ---- commit: one write per touched slot, race-free scatter form ----
    # window_commit redirects non-commit lanes to slot C (out of range,
    # mode="drop"); Pallas refs have no drop mode, so instead every
    # non-commit lane REJOINS the first committing lane's write — same
    # target, same value, so duplicate-scatter order can't matter.  With
    # zero commit lanes (all-pad window) every lane rewrites the raw
    # current value of lane 0's row: a no-op.
    f_tlo, f_thi = _pair_reabs(fin.tstamp, n_lo, n_hi)
    f_elo, f_ehi = _pair_reabs(fin.expire, n_lo, n_hi)
    any_commit = jnp.any(commit_mask)
    safe = jnp.argmax(commit_mask).astype(I32)
    tgt = jnp.where(commit_mask, g, jnp.take(g, safe))
    cm = (commit_mask, any_commit, safe, tgt)

    _commit_ref(o_lim, cm, fin.limit, raw_lim)
    _commit_ref(o_dur, cm, fin.duration, raw_dur)
    _commit_ref(o_rem, cm, fin.remaining, raw_rem)
    _commit_ref(o_tlo, cm, f_tlo, raw_tlo)
    _commit_ref(o_thi, cm, f_thi, raw_thi)
    _commit_ref(o_elo, cm, f_elo, raw_elo)
    _commit_ref(o_ehi, cm, f_ehi, raw_ehi)
    _commit_ref(o_algo, cm, fin.algo, raw_algo)

    # ---- response encode (kernel.encode_output_word image) + unsort ----
    # reset word: enc 0 iff the ABSOLUTE reset is 0 — the leaky no-reset
    # sentinel (rel == 0 on a leaky lane) or an absolute time that lands
    # exactly on zero; otherwise clip(rel, 0, 2^31-2) + 1, exact because
    # reset64 - now == rel in int64
    # leaky AND concurrency use reset 0 as the no-reset sentinel
    leaky0 = (((s_algo == kernel.LEAKY_BUCKET)
               | (s_algo == kernel.CONCURRENCY))
              & (out_sorted.reset_time == 0))
    ab_lo, ab_hi = _pair_reabs(out_sorted.reset_time, n_lo, n_hi)
    reset_zero = leaky0 | ((ab_lo == 0) & (ab_hi == 0))
    enc = jnp.where(reset_zero, jnp.int32(0),
                    jnp.clip(out_sorted.reset_time, 0,
                             jnp.int32(2**31 - 2)) + 1)
    w_lo_s = (out_sorted.status << 31) | jnp.maximum(out_sorted.remaining, 0)
    unsort = lambda v: jnp.zeros_like(v).at[order].set(v)
    w_lo = unsort(w_lo_s)
    w_hi = unsort(enc)
    rlimit = unsort(out_sorted.limit)
    mism = jnp.any((out_sorted.limit != s_limit) & s_valid).astype(I32)
    aux = _FusedAux(order=order, g=g, s_slot=s_slot, s_valid=s_valid,
                    s_hits=s_hits, s_init=s_init, status=out_sorted.status,
                    commit_mask=commit_mask, any_commit=any_commit,
                    safe=safe, tgt=tgt)
    return w_lo, w_hi, rlimit, mism, aux


def _fused_kernel(now_ref, req_ref,
                  a_lim, a_dur, a_rem, a_tlo, a_thi, a_elo, a_ehi, a_algo,
                  o_lim, o_dur, o_rem, o_tlo, o_thi, o_elo, o_ehi, o_algo,
                  o_wlo, o_whi, o_rlimit, o_mism):
    """Single-window fused kernel: one _fused_window_body call.  The a_*
    input refs alias the o_* outputs (and so are never read — the body
    reads the aliased o_* planes, which IS the input arena)."""
    del a_lim, a_dur, a_rem, a_tlo, a_thi, a_elo, a_ehi, a_algo
    w_lo, w_hi, rlimit, mism, _ = _fused_window_body(
        now_ref[0], now_ref[1], req_ref[:],
        (o_lim, o_dur, o_rem, o_tlo, o_thi, o_elo, o_ehi, o_algo))
    o_wlo[...] = w_lo
    o_whi[...] = w_hi
    o_rlimit[...] = rlimit
    o_mism[0] = mism


def window_step_fused_planes(st32: FusedState32, packed, now, *,
                             interpret: bool = False):
    """One compact serving window as ONE pallas_call over the plane-form
    arena.  Returns (new_st32, words i64[B], limits i64[B], mism bool) —
    `words` is exactly kernel.encode_output_word(out, now) and `limits`
    the stored-limit response plane, matching the pipeline drain's wire.

    Exactness contract: identical to decode_batch → window_step (the int64
    oracle) → encode_output_word under the compact wire caps plus
    arena-written-under-caps — the same contract window_step_compact32_xla
    carries, pinned by tests/test_fused_megakernel.py differentials.
    """
    B = packed.shape[0]
    C = st32.limit.shape[0]
    assert B & (B - 1) == 0, "fused megakernel needs power-of-two lanes"
    now = jnp.asarray(now, I64)
    req32 = lax.bitcast_convert_type(packed, I32).reshape(B, 4)
    now32 = lax.bitcast_convert_type(now.reshape((1,)), I32).reshape((2,))

    vma = _vma(packed)
    lane_sds = lambda shape: jax.ShapeDtypeStruct(shape, I32, vma=vma)
    plane_sds = lambda: jax.ShapeDtypeStruct((C,), I32,
                                           vma=_vma(st32.limit))
    bspec = pl.BlockSpec((B,), lambda: (0,))
    aspec = pl.BlockSpec(memory_space=pl.ANY)
    outs = pl.pallas_call(
        _fused_kernel,
        in_specs=[pl.BlockSpec((2,), lambda: (0,)),
                  pl.BlockSpec((B, 4), lambda: (0, 0))] + [aspec] * 8,
        out_specs=[aspec] * 8 + [bspec] * 3
        + [pl.BlockSpec((1,), lambda: (0,))],
        out_shape=[plane_sds() for _ in range(8)]
        + [lane_sds((B,)) for _ in range(3)] + [lane_sds((1,))],
        # arena planes update in place: inputs 2..9 alias outputs 0..7
        input_output_aliases={i + 2: i for i in range(8)},
        interpret=interpret,
    )(now32, req32, *st32)
    new32 = FusedState32(*outs[:8])
    words = lax.bitcast_convert_type(
        jnp.stack([outs[8], outs[9]], axis=-1), I64)
    limits = outs[10].astype(I64)
    return new32, words, limits, outs[11][0] != 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def window_step_fused(state, packed, now, *,
                      interpret: bool = False):
    """State-in/state-out wrapper around the fused megakernel (single-window
    call sites; the state comes back in the form it came in, resident
    planes or int64 rows).  The pipeline drain avoids the per-window
    O(C) plane conversion by carrying FusedState32 through its scan and
    calling window_step_fused_planes directly."""
    st32, words, limits, mism = window_step_fused_planes(
        fused_state_to_planes(state), packed, now, interpret=interpret)
    return fused_state_from_planes(st32, state), words, limits, mism


# ---- the K-grid staged drain: all K windows in ONE pallas_call ------------


def _accumulate_window_stats(aux: _FusedAux, ten, tenant_slots,
                             d_occ, d_over, d_hlo, d_hhi,
                             t_occ, t_over, t_hlo, t_hhi, hdr):
    """Fold one window's analytics contributions into the drain's resident
    stats planes, entirely in-kernel — the i32-halves image of
    analytics.shard_stats's dense / tenant / header accumulation.

    Hit counts are i64 in the oracle (per-lane hits < 2^28, but a window's
    per-slot total can reach B * 2^28 and the drain total K times that), and
    Mosaic has no 64-bit vectors — so hits are summed as SPLIT 14-bit limbs
    (lo14 = hits & 0x3FFF, hi14 = hits >> 14; each limb's window sum stays
    under B * 2^14 ≪ 2^31) and reconstructed into an exact (lo, hi) pair
    via value = lo14_sum + hi14_sum * 2^14 before the pair-add into the
    accumulator planes.  All adds are exact integer ops in both domains, so
    the result is bit-identical to the oracle's i64 scatter-adds.

    The dense per-slot planes accumulate at the window's commit lanes (one
    per valid slot — kernel.segment_structure's commit_mask) over the
    slot's PHYSICAL lane range [phys_start, next_phys): virtual segments
    split on is_init lanes, but the stats group purely by slot, so the
    range sums must span every virtual segment of the slot.  Tenant rows
    and the header use full-plane adds (tenant_slots is small)."""
    B = aux.order.shape[0]
    occ_i = aux.s_valid.astype(I32)
    over_i = jnp.where(aux.s_valid, aux.status, 0)
    hits_m = jnp.where(aux.s_valid, aux.s_hits, 0)
    init_i = (aux.s_init & aux.s_valid).astype(I32)
    lo14 = hits_m & jnp.int32(0x3FFF)
    hi14 = hits_m >> 14
    limb_pair = lambda lo, hi: _p_add((lo, jnp.int32(0)),
                                      (hi << 14, hi >> 18))

    # physical slot boundaries (segment_structure's phys_start lattice,
    # recomputed here because the body only exposes the virtual structure)
    idx = lax.iota(I32, B)
    prev_slot = jnp.take(aux.s_slot, jnp.maximum(idx - 1, 0))
    phys_start = (idx == 0) | (aux.s_slot != prev_slot)
    phys_start_idx = lax.cummax(jnp.where(phys_start, idx, jnp.int32(0)))
    nxt = jnp.minimum(idx + 1, B - 1)
    shifted = jnp.where(jnp.take(phys_start, nxt) & (idx < B - 1),
                        idx + 1, jnp.int32(B))
    next_phys = lax.cummin(shifted, reverse=True)

    def rng_sum(f):
        # sum of f over [phys_start_idx, next_phys) via prefix differences
        cs = jnp.cumsum(f)
        return (jnp.take(cs, next_phys - 1) - jnp.take(cs, phys_start_idx)
                + jnp.take(f, phys_start_idx))

    cm = (aux.commit_mask, aux.any_commit, aux.safe, aux.tgt)
    occ_w = rng_sum(occ_i)
    over_w = rng_sum(over_i)
    w_pair = limb_pair(rng_sum(lo14), rng_sum(hi14))
    cur_occ = d_occ[aux.g]
    cur_over = d_over[aux.g]
    cur_h = (d_hlo[aux.g], d_hhi[aux.g])
    new_h = _p_add(cur_h, w_pair)
    _commit_ref(d_occ, cm, cur_occ + occ_w, cur_occ)
    _commit_ref(d_over, cm, cur_over + over_w, cur_over)
    _commit_ref(d_hlo, cm, new_h[0], cur_h[0])
    _commit_ref(d_hhi, cm, new_h[1], cur_h[1])

    # tenant rows: one-hot masked column sums (no scatter needed — the
    # tenant axis is small), full-plane accumulate
    tid = jnp.clip(jnp.take(ten, aux.order), 0,
                   jnp.int32(tenant_slots - 1))
    oh = (tid[:, None] == lax.iota(I32, tenant_slots)[None, :]).astype(I32)
    col = lambda v: jnp.sum(oh * v[:, None], axis=0, dtype=I32)
    t_occ[...] = t_occ[...] + col(occ_i)
    t_over[...] = t_over[...] + col(over_i)
    t_pair = _p_add((t_hlo[...], t_hhi[...]),
                    limb_pair(col(lo14), col(hi14)))
    t_hlo[...] = t_pair[0]
    t_hhi[...] = t_pair[1]

    # header counters: [lanes, hits_lo, hits_hi, over, init, 0, 0, 0]
    h_pair = _p_add((hdr[1], hdr[2]),
                    limb_pair(jnp.sum(lo14, dtype=I32),
                              jnp.sum(hi14, dtype=I32)))
    hdr[0] = hdr[0] + jnp.sum(occ_i, dtype=I32)
    hdr[1] = h_pair[0]
    hdr[2] = h_pair[1]
    hdr[3] = hdr[3] + jnp.sum(over_i, dtype=I32)
    hdr[4] = hdr[4] + jnp.sum(init_i, dtype=I32)


def _make_drain_kernel(with_stats: bool, tenant_slots: int):
    """Kernel factory for the K-grid drain: grid=(K,), one
    _fused_window_body call per grid step over per-window request blocks,
    with the arena planes carried ACROSS grid steps through the aliased
    ANY-space output refs (step k reads the planes step k-1 committed —
    the in-kernel image of the lax.scan carry it replaces).  With stats,
    nine accumulator planes ride along: zeroed on the first grid step,
    folded per window by _accumulate_window_stats."""
    def drain_kernel(*refs):
        now_ref, req_ref = refs[0], refs[1]
        i = 2
        ten_ref = None
        if with_stats:
            ten_ref = refs[i]
            i += 1
        arena = refs[i + 8:i + 16]   # outputs; refs[i:i+8] are the aliases
        j = i + 16
        o_wlo, o_whi, o_rlimit, o_mism = refs[j:j + 4]
        stats_refs = refs[j + 4:]
        if with_stats:
            @pl.when(pl.program_id(0) == 0)
            def _zero_stats():
                for r in stats_refs:
                    r[...] = jnp.zeros(r.shape, r.dtype)
        w_lo, w_hi, rlimit, mism, aux = _fused_window_body(
            now_ref[0, 0], now_ref[0, 1], req_ref[0], arena)
        o_wlo[0, :] = w_lo
        o_whi[0, :] = w_hi
        o_rlimit[0, :] = rlimit
        o_mism[0] = mism
        if with_stats:
            _accumulate_window_stats(aux, ten_ref[0], tenant_slots,
                                     *stats_refs)
    return drain_kernel


def window_drain_fused_planes(st32: FusedState32, packed, nows, *,
                              interpret: bool = False, tenants=None,
                              tenant_slots: int = 0):
    """The WHOLE K-window compact drain as ONE pallas_call: the K-major
    grid dimension replaces the lax.scan skeleton, so the scan's
    per-iteration slice/convert/stack shoulders vanish from the trace and
    the composed drain executes O(1) kernels total instead of O(K).

    packed i64[K, B, 2], nows i64[K]; returns (new_st32, words i64[K, B],
    limits i64[K, B], mism bool[K], stats) — bit-identical per window to K
    sequential window_step_fused_planes calls (same body, same carry, just
    carried through the grid instead of a scan).

    With `tenants` (i32[K, B]) the drain ALSO folds the analytics
    accumulation in-kernel and `stats` returns the nine i32 planes
    (d_occ/d_over/d_hlo/d_hhi [C], t_occ/t_over/t_hlo/t_hhi [tenant_slots],
    hdr [8]) that analytics.staged_stats_tail finishes into the canonical
    stats vector; otherwise stats is None."""
    K, B = packed.shape[0], packed.shape[1]
    C = st32.limit.shape[0]
    assert B & (B - 1) == 0, "fused megakernel needs power-of-two lanes"
    req32 = lax.bitcast_convert_type(packed, I32).reshape(K, B, 4)
    nows32 = lax.bitcast_convert_type(nows, I32).reshape(K, 2)
    with_stats = tenants is not None

    lane_sds = lambda shape: jax.ShapeDtypeStruct(shape, I32,
                                                vma=_vma(packed))
    plane_sds = lambda shape: jax.ShapeDtypeStruct(
        shape, I32, vma=_vma(st32.limit))
    aspec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, 2), lambda k: (k, 0)),
                pl.BlockSpec((1, B, 4), lambda k: (k, 0, 0))]
    inputs = [nows32, req32]
    if with_stats:
        in_specs.append(pl.BlockSpec((1, B), lambda k: (k, 0)))
        inputs.append(tenants.astype(I32))
    arena_base = len(inputs)
    in_specs += [aspec] * 8
    inputs += list(st32)
    out_specs = ([aspec] * 8
                 + [pl.BlockSpec((1, B), lambda k: (k, 0))] * 3
                 + [pl.BlockSpec((1,), lambda k: (k,))])
    out_shape = ([plane_sds((C,)) for _ in range(8)]
                 + [lane_sds((K, B)) for _ in range(3)]
                 + [lane_sds((K,))])
    if with_stats:
        out_specs += [aspec] * 9
        out_shape += ([plane_sds((C,)) for _ in range(4)]
                      + [plane_sds((tenant_slots,)) for _ in range(4)]
                      + [plane_sds((8,))])
    outs = pl.pallas_call(
        _make_drain_kernel(with_stats, tenant_slots),
        grid=(K,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={arena_base + i: i for i in range(8)},
        interpret=interpret,
    )(*inputs)
    new32 = FusedState32(*outs[:8])
    words = lax.bitcast_convert_type(
        jnp.stack([outs[8], outs[9]], axis=-1), I64)
    limits = outs[10].astype(I64)
    mism = outs[11] != 0
    stats = tuple(outs[12:]) if with_stats else None
    return new32, words, limits, mism, stats


# ---- the analytics finisher: sketch + top-k as ONE kernel -----------------


def _make_stats_finish_kernel(C, D, W, tenant_slots, topk, over_weight):
    """Kernel factory for the staged analytics FINISH: everything
    analytics.staged_stats_tail does in ~110 XLA equations — count-min
    decay + scatter, per-slot estimates, top-k candidate ranking, tenant
    rows, header — as one kernel body (census cost: 1).

    The tricky lowerings, all Mosaic-legal:
      * scatter-add with DUPLICATE hash buckets (np.add.at semantics)
        becomes a one-hot masked sum per sketch row: bucket w receives
        sum_c [h[r, c] == w] * dense_w[c], accumulated in 14-bit limbs so
        the i32 lanes never overflow, recombined into exact i64 pairs;
      * the i64 sketch decays via the variable pair shift (_p_shr);
      * lax.top_k (ties -> lowest index first) becomes the descending
        bitonic network with the index as explicit tiebreak, padded to a
        power of two with INT64_MIN scores.
    over_weight enters as static 14-bit limbs so dense_w = dense_h +
    over_weight * dense_o stays exact in pair space for any config value
    (dense_o is a lane count, < 2^17 for every real geometry)."""
    N = 1
    while N < C:
        N *= 2
    ow = int(over_weight)
    ow_limbs = [(ow >> (14 * j)) & 0x3FFF for j in range(4)] + [
        (ow >> 56) & 0xFF]

    def kern(now_ref, dk_ref, h_ref, docc_ref, dover_ref, dhlo_ref,
             dhhi_ref, tocc_ref, tover_ref, thlo_ref, thhi_ref, hdr_ref,
             exp_ref, a_sk_ref, o_sk_ref, o_stats_ref):
        del a_sk_ref  # aliased: o_sk_ref initializes from it
        now = (now_ref[0, 0], now_ref[0, 1])
        dk = dk_ref[0]
        docc, dover = docc_ref[...], dover_ref[...]
        dh = (dhlo_ref[...], dhhi_ref[...])

        # dense_w = dense_h + over_weight * dense_o, exact via limb products
        dw = _p_add(dh, _p_from_limbs(*[dover * l for l in ow_limbs]))
        limbs = _p_limbs(dw)

        # sketch rows: decay, duplicate-safe scatter-add, per-slot estimate
        iota_wc = lax.broadcasted_iota(I32, (W, C), 0)
        est = None
        for r in range(D):
            hr = h_ref[r]
            onehot = (iota_wc == hr[None, :]).astype(I32)
            sums = [jnp.sum(onehot * l[None, :], axis=1, dtype=I32)
                    for l in limbs]
            contrib = _p_from_limbs(*sums)
            old = (o_sk_ref[r, :, 0], o_sk_ref[r, :, 1])
            new = _p_add(_p_shr(old, dk), contrib)
            o_sk_ref[r] = jnp.stack([new[0], new[1]], axis=-1)
            est_r = (jnp.take(new[0], hr), jnp.take(new[1], hr))
            est = est_r if est is None else _p_min(est, est_r)

        # top-k by estimate over touched slots (untouched score -1), ties
        # to the LOWER slot — lax.top_k semantics, which the candidate
        # table's rolling host merge relies on
        touched = docc > 0
        s_lo = jnp.where(touched, est[0], -1)
        s_hi = jnp.where(touched, est[1], -1)
        lane = lax.iota(I32, N)
        if N > C:
            pad_lo = jnp.zeros((N - C,), I32)
            pad_hi = jnp.full((N - C,), -2147483648, I32)
            s_lo = jnp.concatenate([s_lo, pad_lo])
            s_hi = jnp.concatenate([s_hi, pad_hi])
        key_lo, key_hi, idx = s_lo, s_hi, lane
        k = 2
        while k <= N:
            j = k // 2
            while j >= 1:
                partner = lane ^ j
                p_lo = jnp.take(key_lo, partner)
                p_hi = jnp.take(key_hi, partner)
                p_idx = jnp.take(idx, partner)
                kp, pp = (key_lo, key_hi), (p_lo, p_hi)
                prec = _p_lt(pp, kp) | (_p_eq(kp, pp) & (idx < p_idx))
                ascending = (lane & k) == 0
                is_lower = (lane & j) == 0
                keep = jnp.where(is_lower, prec == ascending,
                                 prec != ascending)
                key_lo = jnp.where(keep, key_lo, p_lo)
                key_hi = jnp.where(keep, key_hi, p_hi)
                idx = jnp.where(keep, idx, p_idx)
                j //= 2
            k *= 2
        top_slot = idx[:topk]
        top = (key_lo[:topk], key_hi[:topk])
        valid = top[1] >= 0
        c_slot = _p_where(valid, _p_sext(top_slot), (-1, -1))
        c_est = _p_where(valid, top, _P0)
        c_h = _p_where(valid, (jnp.take(dh[0], top_slot),
                               jnp.take(dh[1], top_slot)), _P0)
        c_o = _p_where(valid, _p_sext(jnp.take(dover, top_slot)), _P0)
        cand_lo = jnp.stack([c_slot[0], c_est[0], c_h[0], c_o[0]], axis=-1)
        cand_hi = jnp.stack([c_slot[1], c_est[1], c_h[1], c_o[1]], axis=-1)

        tocc, tover = tocc_ref[...], tover_ref[...]
        t_lo = jnp.stack([tocc, thlo_ref[...], tover], axis=-1)
        t_hi = jnp.stack([tocc >> 31, thhi_ref[...], tover >> 31], axis=-1)

        exp = (exp_ref[:, 0], exp_ref[:, 1])
        live = jnp.sum(_p_lt(now, exp).astype(I32), dtype=I32)
        expd = jnp.sum(((~_p_is0(exp)) & ~_p_lt(now, exp)).astype(I32),
                       dtype=I32)
        hdr = hdr_ref[...]
        lanes, over, init = hdr[0], hdr[3], hdr[4]
        under = lanes - over
        zero = jnp.zeros_like(lanes)
        head_lo = jnp.stack([lanes, hdr[1], under, over, init,
                             live, expd, zero])
        head_hi = jnp.stack([lanes >> 31, hdr[2], under >> 31, over >> 31,
                             init >> 31, zero, zero, zero])

        Tn = tenant_slots
        o_stats_ref[0:8] = jnp.stack([head_lo, head_hi], axis=-1)
        o_stats_ref[8:8 + 3 * Tn] = jnp.stack(
            [t_lo.reshape(3 * Tn), t_hi.reshape(3 * Tn)], axis=-1)
        o_stats_ref[8 + 3 * Tn:] = jnp.stack(
            [cand_lo.reshape(4 * topk), cand_hi.reshape(4 * topk)], axis=-1)

    return kern


def staged_stats_finish(sketch, drain_stats, expire, now, decay, *,
                        tenant_slots: int, topk: int, over_weight: int,
                        interpret: bool = False):
    """analytics.staged_stats_tail as ONE pallas_call — the composed
    drain's analytics finish at census cost ~8 instead of ~110.  Consumes
    the drain kernel's nine i32 stats planes plus the resident sketch
    (aliased: decayed and accumulated in place) and returns the SAME
    (new_sketch i64[D, W], stats i64[8 + 3*tenant_slots + 4*topk]) pair,
    bit-identical to the XLA tail — pinned by the staging differential
    suites.  The hash lattice is data-independent, so it enters as ONE
    device constant ([D, C] i32) rather than traced equations."""
    from gubernator_tpu.ops.analytics import hash_slots
    D, W = sketch.shape
    C = drain_stats[0].shape[0]
    h_np = np.stack([hash_slots(np, np.arange(C, dtype=np.int64), r, W)
                     for r in range(D)]).astype(np.int32)
    pc = lambda a: lax.bitcast_convert_type(a, I32)
    now32 = pc(jnp.reshape(now, (1,)))
    dk32 = jnp.reshape(decay, (1,)).astype(I32)
    sk32 = pc(sketch)
    vma = _vma(drain_stats[0])
    L = 8 + 3 * tenant_slots + 4 * topk
    aspec = pl.BlockSpec(memory_space=pl.ANY)
    new_sk, stats32 = pl.pallas_call(
        _make_stats_finish_kernel(C, D, W, tenant_slots, topk, over_weight),
        in_specs=[aspec] * 14,
        out_specs=[aspec] * 2,
        out_shape=[jax.ShapeDtypeStruct((D, W, 2), I32, vma=vma),
                   jax.ShapeDtypeStruct((L, 2), I32, vma=vma)],
        input_output_aliases={13: 0},
        interpret=interpret,
    )(now32, dk32, jnp.asarray(h_np), *drain_stats, pc(expire), sk32)
    p64 = lambda a: lax.bitcast_convert_type(a, I64)
    return p64(new_sk), p64(stats32)


# ---- the staged GLOBAL ladder: transition as (lo, hi) pair arithmetic -----


def _pair_transition(ent, h, req_limit, req_duration, req_algo, now, fresh,
                     rate, leak):
    """kernel.transition's non-AGG ladder on (lo, hi) i32 pairs — the
    Mosaic-legal form of the FULL-i64 GLOBAL state machine (the GLOBAL
    arena is exempt from the compact caps, so the rebased-i32 trick the
    serving window uses would not be exact here).  Every value except the
    algorithm/status columns is a pair; the two integer divisions (rate,
    leak — Mosaic has no 64-bit divide either) arrive precomputed from
    kernel.transition_precompute, which is exact because both depend only
    on pre-psum data.  Line-for-line in lockstep with transition above."""
    L, D, R, T, E, A = ent
    is_token = req_algo == kernel.TOKEN_BUCKET
    OVER, UNDER = kernel.OVER_LIMIT, kernel.UNDER_LIMIT

    # ---- init path ----
    over_init = _p_lt(req_limit, h)           # h > req_limit
    init_R = _p_where(over_init, _P0, _p_sub(req_limit, h))
    init_status = jnp.where(over_init, OVER, UNDER).astype(I32)
    now_rd = _p_add(now, req_duration)
    init_T = _p_where(is_token, now_rd, now)

    # ---- token bucket hit path ----
    tb_at_zero = _p_is0(R)
    tb_read = _p_is0(h)
    tb_drain = _p_eq(h, R)
    tb_over = _p_lt(R, h)
    R_h = _p_sub(R, h)
    t_status = kernel._chain(
        [(tb_at_zero, OVER), (tb_read, UNDER), (tb_drain, UNDER),
         (tb_over, OVER)], UNDER).astype(I32)
    t_resp_R = _p_chain(
        [(tb_at_zero, _P0), (tb_read, R), (tb_drain, _P0), (tb_over, R)],
        R_h)
    t_new_R = _p_chain(
        [(tb_at_zero, R), (tb_read, R), (tb_drain, _P0), (tb_over, R)],
        R_h)

    # ---- leaky bucket hit path ----
    R2 = _p_add(R, _p_min(leak, _p_sub(L, R)))
    T2 = _p_where(_p_is0(h), T, now)
    lb_at_zero = _p_is0(R2)
    lb_drain = _p_eq(h, R2)
    lb_over = _p_lt(R2, h)
    lb_read = _p_is0(h)
    now_rate = _p_add(now, rate)
    l_status = kernel._chain(
        [(lb_at_zero, OVER), (lb_drain, UNDER), (lb_over, OVER),
         (lb_read, UNDER)], UNDER).astype(I32)
    R2_h = _p_sub(R2, h)
    l_resp_R = _p_chain(
        [(lb_at_zero, _P0), (lb_drain, _P0), (lb_over, R2), (lb_read, R2)],
        R2_h)
    l_reset = _p_chain(
        [(lb_at_zero, now_rate), (lb_drain, _P0), (lb_over, now_rate),
         (lb_read, _P0)], _P0)
    l_new_R = _p_chain(
        [(lb_at_zero, R2), (lb_drain, _P0), (lb_over, R2), (lb_read, R2)],
        R2_h)
    l_hit = ~(lb_at_zero | lb_drain | lb_over | lb_read)
    l_new_E = _p_where(l_hit, now_rd, E)

    # ---- combine ----
    pw = lambda t, l: _p_where(is_token, t, l)
    hit_R = pw(t_new_R, l_new_R)
    hit_T = pw(T, T2)
    hit_E = pw(E, l_new_E)
    hit_status = jnp.where(is_token, t_status, l_status)
    hit_resp_R = pw(t_resp_R, l_resp_R)
    hit_reset = pw(T, l_reset)

    fw = lambda i, hh: _p_where(fresh, i, hh)
    new_reg = _Reg(
        limit=fw(req_limit, L),
        duration=fw(req_duration, D),
        remaining=fw(init_R, hit_R),
        tstamp=fw(init_T, hit_T),
        expire=fw(now_rd, hit_E),
        algo=jnp.where(fresh, req_algo, A),
    )
    out = WindowOutput(
        status=jnp.where(fresh, init_status, hit_status),
        limit=fw(req_limit, L),
        remaining=fw(init_R, hit_resp_R),
        reset_time=fw(_p_where(is_token, now_rd, _P0), hit_reset),
    )
    return new_reg, out


def _global_kernel(now_ref, bi32_ref, bi64_ref, gi32_ref, gi64_ref, rl_ref,
                   o_lim, o_dur, o_rem, o_ts, o_exp, o_algo, o_read):
    """kernel.global_read + kernel.global_apply as ONE kernel body: the
    replica-read gather, both freshness tests, the [Bg|G] lane concat, the pair transition
    ladder and the touched-merge apply — everything between the psum and
    the outputs.  Operands arrive PACKED (one concat + one bitcast per
    dtype class on the XLA side, sliced apart here where slicing is free):
    bi32 [3*Bg] = slot|algo|is_init, bi64 [3*Bg, 2] = hits|limit|duration,
    gi32 [2*G] = state.algo|cfg.algo, gi64 [8*G, 2] = state limit|duration|
    remaining|tstamp|expire then cfg limit|duration then summed, rl
    [2*(Bg+G), 2] = rate|leak.  o_read [Bg, 4, 2] is the read half already
    in the fused response layout (status|limit|remaining|reset pairs) —
    one bitcast away from the wire's gfused block."""
    now = (now_ref[0, 0], now_ref[0, 1])
    G = gi32_ref.shape[0] // 2
    Bg = bi32_ref.shape[0] // 3
    bi32, bi64 = bi32_ref[...], bi64_ref[...]
    gi32, gi64 = gi32_ref[...], gi64_ref[...]
    slot, b_algo = bi32[:Bg], bi32[Bg:2 * Bg]
    b_init = bi32[2 * Bg:]
    bp = lambda i: (bi64[i * Bg:(i + 1) * Bg, 0],
                    bi64[i * Bg:(i + 1) * Bg, 1])
    b_hits, b_lim, b_dur = bp(0), bp(1), bp(2)
    gp = lambda i: (gi64[i * G:(i + 1) * G, 0], gi64[i * G:(i + 1) * G, 1])
    st_lim, st_dur, st_rem, st_ts, st_exp = (gp(0), gp(1), gp(2), gp(3),
                                             gp(4))
    c_lim, c_dur, summed = gp(5), gp(6), gp(7)
    st_algo, c_algo = gi32[:G], gi32[G:]
    n = Bg + G
    rl = rl_ref[...]
    rate = (rl[:n, 0], rl[:n, 1])
    leak = (rl[n:, 0], rl[n:, 1])

    g = jnp.clip(slot, 0, G - 1)
    gt = lambda p: (jnp.take(p[0], g), jnp.take(p[1], g))
    r_exp = gt(st_exp)
    r_algo = jnp.take(st_algo, g)
    r_fresh = (b_init != 0) | _p_lt(r_exp, now) | (b_algo != r_algo)
    a_fresh = _p_lt(st_exp, now) | (c_algo != st_algo)

    catp = lambda a, b: (jnp.concatenate([a[0], b[0]]),
                         jnp.concatenate([a[1], b[1]]))
    cat = jnp.concatenate
    ent = _Reg(
        limit=catp(gt(st_lim), st_lim),
        duration=catp(gt(st_dur), st_dur),
        remaining=catp(gt(st_rem), st_rem),
        tstamp=catp(gt(st_ts), st_ts),
        expire=catp(r_exp, st_exp),
        algo=cat([r_algo, st_algo]),
    )
    h = catp(_p_where(r_fresh, b_hits, _P0), summed)
    new_reg, out = _pair_transition(
        ent, h,
        catp(b_lim, c_lim),
        catp(b_dur, c_dur),
        cat([b_algo, c_algo]),
        now,
        cat([r_fresh, a_fresh]),
        rate, leak)

    # read half: the first Bg lanes' responses, in fused response order
    take_bg = lambda p: (p[0][:Bg], p[1][:Bg])
    rlim, rrem, rres = (take_bg(out.limit), take_bg(out.remaining),
                        take_bg(out.reset_time))
    status = out.status[:Bg]
    o_read[...] = jnp.stack(
        [jnp.stack([status, rlim[0], rrem[0], rres[0]], axis=-1),
         jnp.stack([jnp.zeros_like(status), rlim[1], rrem[1], rres[1]],
                   axis=-1)], axis=-1)

    # apply half: the last G lanes' registers, merged on touched slots
    touched = ~_p_is0(summed)
    ap = lambda p: (p[0][Bg:], p[1][Bg:])
    mg = lambda new, old: _p_where(touched, new, old)
    w2 = lambda ref, p: ref.__setitem__(
        Ellipsis, jnp.stack([p[0], p[1]], axis=-1))
    w2(o_lim, mg(ap(new_reg.limit), st_lim))
    w2(o_dur, mg(ap(new_reg.duration), st_dur))
    w2(o_rem, mg(ap(new_reg.remaining), st_rem))
    w2(o_ts, mg(ap(new_reg.tstamp), st_ts))
    w2(o_exp, mg(ap(new_reg.expire), st_exp))
    o_algo[...] = jnp.where(touched, new_reg.algo[Bg:], st_algo)


@functools.partial(jax.jit, static_argnames=("interpret", "fused_out"))
def global_combined_staged(state: BucketState, cfg: GlobalConfig,
                           batch: WindowBatch, summed_hits, now, *,
                           interpret: bool = False, fused_out: bool = False):
    """kernel.global_read followed by kernel.global_apply as ONE pallas_call
    (plus the two hoisted int64 divisions in XLA): the GLOBAL sub-window's
    ~200-equation transition ladder collapses to a single kernel, which is
    what takes the composed drain's census from tens to single digits.
    Bit-exact with that pair for EVERY i64 input (the pair ops are
    exact two's-complement images, wrap included) — pinned by
    tests/test_fused_megakernel.py differentials.

    Same-dtype operands cross as ONE concat + ONE bitcast (the census
    counts every surviving XLA op, so nineteen per-field bitcasts would
    hand back much of what folding the ladder saved).  With
    `fused_out=True` the read half returns as the drain wire's gfused
    block i64[Bg, 4] (status|limit|remaining|reset) straight from the
    kernel — the composed drain ships it without a single stacking op;
    otherwise it unpacks to the legacy WindowOutput."""
    G = state.limit.shape[0]
    now = jnp.asarray(now, I64)
    # the only non-pair-legal ops in the ladder: two int64 floor-divides,
    # batched over the [Bg|G] concat (they read pre-psum data only)
    g = jnp.clip(batch.slot, 0, G - 1)
    rate, leak = kernel.transition_precompute(
        jnp.concatenate([state.duration[g], state.duration]),
        jnp.concatenate([state.tstamp[g], state.tstamp]),
        jnp.concatenate([batch.limit, cfg.limit]),
        now)

    pc = lambda a: lax.bitcast_convert_type(a, I32)      # i64[n] -> [n, 2]
    now32 = pc(now.reshape((1,)))
    bi32 = jnp.concatenate([batch.slot, batch.algo,
                            batch.is_init.astype(I32)])
    bi64 = pc(jnp.concatenate([batch.hits, batch.limit, batch.duration]))
    gi32 = jnp.concatenate([state.algo, cfg.algo])
    gi64 = pc(jnp.concatenate([state.limit, state.duration, state.remaining,
                               state.tstamp, state.expire, cfg.limit,
                               cfg.duration, summed_hits]))
    rl = pc(jnp.concatenate([rate, leak]))
    vma_b = _vma(batch.slot)
    vma_s = _vma(state.limit)
    Bg = batch.slot.shape[0]
    sds = lambda shape, vma: jax.ShapeDtypeStruct(shape, I32, vma=vma)
    full = pl.BlockSpec(memory_space=pl.ANY)
    outs = pl.pallas_call(
        _global_kernel,
        in_specs=[full] * 6,
        out_specs=[full] * 7,
        out_shape=([sds((G, 2), vma_s)] * 5 + [sds((G,), vma_s)]
                   + [sds((Bg, 4, 2), vma_b)]),
        interpret=interpret,
    )(now32, bi32, bi64, gi32, gi64, rl)
    p64 = lambda a: lax.bitcast_convert_type(a, I64)     # [n, 2] -> i64[n]
    new_state = BucketState(
        limit=p64(outs[0]), duration=p64(outs[1]), remaining=p64(outs[2]),
        tstamp=p64(outs[3]), expire=p64(outs[4]), algo=outs[5])
    read64 = p64(outs[6])                                # [Bg, 4]
    if fused_out:
        return new_state, read64
    read_out = WindowOutput(
        status=read64[:, 0].astype(I32), limit=read64[:, 1],
        remaining=read64[:, 2], reset_time=read64[:, 3])
    return new_state, read_out
