"""step_stacked (K request windows per dispatch) must equal K sequential
step() calls — through BOTH routing backends (Python SlotTable and the C++
router's drain protocol), including GLOBAL lanes and cross-window key
reuse.  This is the lockstep saturation path (the mesh analog of the
reference's back-to-back queue drain, peers.go:143-172)."""

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401
from gubernator_tpu import native
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.core.engine import RateLimitEngine

T0 = 1_700_000_000_000


def make_engine(use_native, **kw):
    return RateLimitEngine(
        capacity_per_shard=64,
        batch_per_shard=16,
        global_capacity=32,
        global_batch_per_shard=8,
        max_global_updates=8,
        use_native=use_native,
        **kw,
    )


def random_windows(rng, k=4, per_window=24):
    wins = []
    for _ in range(k):
        reqs = []
        for _ in range(per_window):
            if rng.random() < 0.15:
                reqs.append(RateLimitReq(
                    name="ssg", unique_key=f"g{rng.integers(0, 4)}",
                    hits=int(rng.integers(0, 3)), limit=50,
                    duration=60_000, behavior=Behavior.GLOBAL))
            else:
                reqs.append(RateLimitReq(
                    name="ss", unique_key=f"k{rng.integers(0, 30)}",
                    hits=int(rng.integers(0, 3)), limit=10,
                    duration=60_000,
                    algorithm=int(rng.integers(0, 2))))
        wins.append(reqs)
    return wins


@pytest.mark.parametrize("use_native", [
    False,
    pytest.param("on", marks=pytest.mark.skipif(
        not native.available(), reason="native router unavailable")),
])
@pytest.mark.parametrize("seed", [0, 1])
def test_stacked_requests_equal_sequential(use_native, seed):
    rng = np.random.default_rng(seed)
    wins = random_windows(rng)

    ea = make_engine(use_native)
    want = [ea.step(w, now=T0) for w in wins]

    eb = make_engine(use_native)
    got = eb.step_stacked(wins, now=T0)

    for k, (gw, ww) in enumerate(zip(got, want)):
        for j, (g, r) in enumerate(zip(gw, ww)):
            assert (g.status, g.limit, g.remaining, g.reset_time) == \
                (r.status, r.limit, r.remaining, r.reset_time), (k, j)


@pytest.mark.skipif(not native.available(),
                    reason="native router unavailable")
def test_stacked_key_first_seen_mid_stack():
    """A key allocated in window 1 must report is_init exactly once across
    the stack (the drain protocol), so window 2's hit decrements instead of
    re-initializing."""
    eng = make_engine("on")
    req = RateLimitReq(name="mid", unique_key="x", hits=1, limit=5,
                       duration=60_000)
    got = eng.step_stacked([[], [req], [req]], now=T0)
    assert [r.remaining for w in got for r in w] == [4, 3]


def test_stacked_pads_to_k_stack():
    eng = make_engine(False)
    req = RateLimitReq(name="pad", unique_key="p", hits=1, limit=5,
                       duration=60_000)
    got = eng.step_stacked([[req]], now=T0, k_stack=4)
    assert got[0][0].remaining == 4
    # the stack dispatched as ONE device call carrying 4 windows
    assert eng.windows_processed == 4


def test_stacked_global_lanes_match_sequential():
    eng = make_engine(False)
    ref = make_engine(False)
    reqs = [RateLimitReq(name="sg", unique_key="hot", hits=1, limit=20,
                         duration=60_000, behavior=Behavior.GLOBAL,
                         algorithm=Algorithm.TOKEN_BUCKET)]
    want = [ref.step(reqs, now=T0), ref.step(reqs, now=T0 + 1)]
    # stacked GLOBAL semantics across windows share the same psum cadence:
    # window 1's read sees window 0's applied hits
    got = eng.step_stacked([reqs, reqs], now=T0)
    assert got[0][0].remaining == want[0][0].remaining
    # window 1 sees the psum-applied hit from window 0 (one decrement)
    assert got[1][0].remaining == want[1][0].remaining


def _inert_stack(eng, k):
    """A K-window stack with zero GLOBAL lanes and inert control — the
    shape step_windows routes to the GLOBAL-skipping executable."""
    import numpy as np

    from gubernator_tpu.core.engine import WindowBatch
    from gubernator_tpu.ops import kernel

    SL, B = eng.num_local_shards, eng.batch_per_shard
    gb, ga, upd, ups = eng.empty_control()
    stk = lambda a: np.stack([a] * k)  # noqa: E731
    batches = WindowBatch(
        slot=np.full((k, SL, B), kernel.PAD_SLOT, np.int32),
        hits=np.zeros((k, SL, B), np.int64),
        limit=np.zeros((k, SL, B), np.int64),
        duration=np.zeros((k, SL, B), np.int64),
        algo=np.zeros((k, SL, B), np.int32),
        is_init=np.zeros((k, SL, B), bool))
    return (batches, WindowBatch(*[stk(a) for a in gb]), stk(ga),
            upd, ups, np.full((k,), T0, np.int64))


def test_empty_global_skip_census():
    """The GLOBAL-skipping stacked variant must trace to strictly fewer
    equations than the composed twin, and to no collective: the per-window
    GLOBAL gathers, scatters and psum are gone, and the once-per-stack
    control apply is gone too."""
    import jax

    from gubernator_tpu.core import engine as eng_mod

    from .harness import count_eqns

    eng = make_engine(False)
    args = _inert_stack(eng, 2)
    full = jax.make_jaxpr(eng_mod._compiled_multi_step(eng.mesh))(
        eng.state, eng.gstate, eng.gcfg, *args)
    skip = jax.make_jaxpr(
        eng_mod._compiled_multi_step(eng.mesh, with_global=False))(
        eng.state, eng.gstate, eng.gcfg, *args)
    cf, cs = count_eqns(full), count_eqns(skip)
    assert cs < cf, (
        f"GLOBAL-skip variant traces to {cs} equations, composed to {cf}")
    assert "psum" in str(full) and "psum" not in str(skip)


def test_empty_global_skip_matches_sequential(monkeypatch):
    """A no-GLOBAL stack must route to the skipping executable AND stay
    bit-identical to sequential step() — the zero-filled GLOBAL rows in
    the fused output never reach a response."""
    from gubernator_tpu.core import engine as eng_mod

    picked = []
    real = eng_mod._compiled_multi_step

    def spy(mesh, with_global=True):
        picked.append(with_global)
        return real(mesh, with_global=with_global)

    monkeypatch.setattr(eng_mod, "_compiled_multi_step", spy)

    rng = np.random.default_rng(7)
    wins = [[RateLimitReq(name="nog", unique_key=f"k{rng.integers(0, 20)}",
                          hits=int(rng.integers(0, 3)), limit=10,
                          duration=60_000,
                          algorithm=int(rng.integers(0, 2)))
             for _ in range(16)] for _ in range(3)]

    ref = make_engine(False)
    want = [ref.step(w, now=T0) for w in wins]
    eng = make_engine(False)
    got = eng.step_stacked(wins, now=T0)

    assert False in picked, "no-GLOBAL stack never took the skip variant"
    for k, (gw, ww) in enumerate(zip(got, want)):
        for j, (g, r) in enumerate(zip(gw, ww)):
            assert (g.status, g.limit, g.remaining, g.reset_time) == \
                (r.status, r.limit, r.remaining, r.reset_time), (k, j)


def test_global_stack_keeps_composed_variant(monkeypatch):
    """Any live GLOBAL lane (or non-inert control) must keep the composed
    executable — the skip gate is for provably-inert stacks only."""
    from gubernator_tpu.core import engine as eng_mod

    picked = []
    real = eng_mod._compiled_multi_step

    def spy(mesh, with_global=True):
        picked.append(with_global)
        return real(mesh, with_global=with_global)

    monkeypatch.setattr(eng_mod, "_compiled_multi_step", spy)

    eng = make_engine(False)
    reqs = [RateLimitReq(name="gg", unique_key="h", hits=1, limit=20,
                         duration=60_000, behavior=Behavior.GLOBAL)]
    eng.step_stacked([reqs, reqs], now=T0)
    assert False not in picked, (
        "stack with live GLOBAL lanes routed to the skip variant")


def test_skip_global_static_twin(monkeypatch):
    """skip_global=True is a config-level promise of zero GLOBAL traffic:
    every stacked dispatch lowers to the GLOBAL-skipping twin without
    inspecting the stack.  The choice derives from config alone, so every
    mesh process makes it identically — mesh-legal where the per-stack
    inertness gate is not.  Results stay bit-identical to sequential
    step(), and a live GLOBAL lane under the promise raises loudly."""
    from gubernator_tpu.core import engine as eng_mod

    rng = np.random.default_rng(11)
    wins = [[RateLimitReq(name="sgc", unique_key=f"k{rng.integers(0, 20)}",
                          hits=int(rng.integers(0, 3)), limit=10,
                          duration=60_000,
                          algorithm=int(rng.integers(0, 2)))
             for _ in range(16)] for _ in range(3)]
    ref = make_engine(False)
    want = [ref.step(w, now=T0) for w in wins]

    # construct BEFORE installing the spy: __init__ caches the composed
    # default; every fetch observed below is a step_windows routing choice
    eng = make_engine(False, skip_global=True)

    picked = []
    real = eng_mod._compiled_multi_step

    def spy(mesh, with_global=True):
        picked.append(with_global)
        return real(mesh, with_global=with_global)

    monkeypatch.setattr(eng_mod, "_compiled_multi_step", spy)

    got = eng.step_stacked(wins, now=T0)
    assert picked and True not in picked, picked
    for k, (gw, ww) in enumerate(zip(got, want)):
        for j, (g, r) in enumerate(zip(gw, ww)):
            assert (g.status, g.limit, g.remaining, g.reset_time) == \
                (r.status, r.limit, r.remaining, r.reset_time), (k, j)

    greq = [RateLimitReq(name="sgv", unique_key="h", hits=1, limit=20,
                         duration=60_000, behavior=Behavior.GLOBAL)]
    with pytest.raises(ValueError, match="skip_global"):
        eng.step_stacked([greq], now=T0 + 1)
