"""Live key migration on ring change (state/migrate.py + cluster grow/shrink).

A 3-node loopback cluster takes traffic, then the ring grows to 4: ONLY the
keys whose consistent-hash owner changed may move — they must land on the
new owner with remaining/reset_time intact, every unmoved key must stay in
its original slot on its original node, and re-homed GLOBAL keys must
re-register (config + state) on the new owner while the source keeps its
replica.  The shrink path then retires the new node and its keys re-home to
the survivors with state preserved again.

Runs on the forced 8-device CPU mesh (conftest.py); engines route in
Python (EngineConfig use_native=False) because migration needs key strings.
"""

import asyncio
import socket

import pytest

import gubernator_tpu  # noqa: F401
from gubernator_tpu import cluster as cluster_mod
from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    Status,
)
from gubernator_tpu.client import AsyncClient
from gubernator_tpu.config import BehaviorConfig, EngineConfig
from gubernator_tpu.core.engine import shard_of

pytestmark = pytest.mark.snapshot

N_KEYS = 40
N_GLOBAL = 24
LIMIT = 10
DURATION = 60_000


@pytest.fixture(scope="module")
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


@pytest.fixture(scope="module")
def cluster(loop):
    c = loop.run_until_complete(cluster_mod.start_with(
        ["127.0.0.1:0"] * 3,
        behaviors=BehaviorConfig(global_sync_wait=0.05),
        engine=EngineConfig(
            capacity_per_shard=512, batch_per_shard=128,
            global_capacity=128, global_batch_per_shard=32,
            max_global_updates=32, use_native=False),
    ))
    yield c
    loop.run_until_complete(c.stop())


def run(loop, coro):
    return loop.run_until_complete(asyncio.wait_for(coro, timeout=120))


def req(key, hits=1, behavior=Behavior.BATCHING):
    return RateLimitReq(name="mig", unique_key=key, hits=hits, limit=LIMIT,
                        duration=DURATION, algorithm=Algorithm.TOKEN_BUCKET,
                        behavior=behavior)


def _owners(cluster, full_keys):
    """hash_key -> owning address under the CURRENT ring (any node's picker
    answers; the membership is identical everywhere)."""
    inst = cluster.nodes[0].instance
    return {k: inst.get_peer(k).host for k in full_keys}


def _holder_addresses(cluster, full_key):
    """Addresses of nodes whose engine holds `full_key` in a regular table."""
    out = []
    for node in cluster.nodes:
        eng = node.instance.engine
        s = shard_of(full_key, eng.num_shards)
        if eng.tables[s].peek(full_key) is not None:
            out.append(node.address)
    return out


def _slot_of(cluster, address, full_key):
    node = next(n for n in cluster.nodes if n.address == address)
    eng = node.instance.engine
    return eng.tables[shard_of(full_key, eng.num_shards)].peek(full_key)


def _ring_of(cluster, addresses):
    """A scratch ring over `addresses`: address that owns a hash key."""
    ring = cluster.nodes[0].instance._picker.new()
    for a in addresses:
        ring.add(a, a)
    return ring.get


def _joining_address(cluster):
    """An address for the node that joins, bound to nothing yet, whose arc
    of the ring is neither a sliver nor most of it.  The ring has one point
    a host (crc32 of its address), so an arbitrary port may leave the
    joining node next to nothing; which keys re-home must not hang on
    that."""
    for _ in range(200):
        with socket.socket() as sock:
            sock.bind(("127.0.0.1", 0))
            address = f"127.0.0.1:{sock.getsockname()[1]}"
        owner = _ring_of(cluster, cluster.addresses + [address])
        share = sum(owner(f"mig_probe:{i}") == address
                    for i in range(400)) / 400
        if 0.1 <= share <= 0.6:
            return address
    raise AssertionError("no port gave the joining node a fair arc")


def _pick_keys(prefix, n, n_moving, owner_after, joining):
    """`n` keys of which exactly `n_moving` re-home to `joining`."""
    moving, staying, i = [], [], 0
    while len(moving) < n_moving or len(staying) < n - n_moving:
        key = f"{prefix}:{i}"
        i += 1
        if owner_after(f"mig_{key}") == joining:
            if len(moving) < n_moving:
                moving.append(key)
        elif len(staying) < n - n_moving:
            staying.append(key)
    return sorted(moving + staying)


def test_ring_grow_migrates_only_rehomed_keys(cluster, loop):
    # the membership after the join is fixed before a key is chosen, so
    # that a quarter of the keys of either kind re-homes whatever ports
    # the run drew
    joining = _joining_address(cluster)
    owner_after = _ring_of(cluster, cluster.addresses + [joining])
    keys = _pick_keys("acct", N_KEYS, N_KEYS // 4, owner_after, joining)
    gkeys = _pick_keys("gacct", N_GLOBAL, N_GLOBAL // 4, owner_after,
                       joining)
    full = {k: f"mig_{k}" for k in keys}
    gfull = {k: f"mig_{k}" for k in gkeys}

    async def seed():
        client = AsyncClient(cluster.get_peer())
        reset = {}
        for k in keys:
            for _ in range(3):
                r = (await client.get_rate_limits([req(k)]))[0]
                assert r.error == "" and r.status == Status.UNDER_LIMIT
            reset[k] = r.reset_time
        for k in gkeys:
            for _ in range(2):
                r = (await client.get_rate_limits(
                    [req(k, behavior=Behavior.GLOBAL)]))[0]
                assert r.error == ""
        # let GLOBAL async forwards reconcile before the ring changes
        await asyncio.sleep(0.3)
        await client.close()
        return reset

    reset_time = run(loop, seed())

    owners_before = _owners(cluster, list(full.values()))
    slot_before = {k: _slot_of(cluster, owners_before[full[k]], full[k])
                   for k in keys}
    for k in keys:
        assert slot_before[k] is not None, f"{k} not resident on its owner"

    # freshest live GLOBAL replica per key across the founding nodes: the
    # state migration is expected to deliver (ties on expire can differ in
    # remaining across replicas, so keep every candidate at max expire)
    gstate_before = {}
    for node in cluster.nodes:
        for k in gkeys:
            rows = node.instance.engine.export_global_rows([gfull[k]])
            if not rows or rows[0]["expire"] == 0 or rows[0]["cfg_limit"] == 0:
                continue
            row = (rows[0]["remaining"], rows[0]["expire"],
                   rows[0]["cfg_limit"])
            cands = gstate_before.setdefault(k, set())
            best = max((e for _, e, _ in cands), default=0)
            if row[1] > best:
                gstate_before[k] = {row}
            elif row[1] == best:
                cands.add(row)

    added = run(loop, cluster.add_instance(joining))
    assert len(cluster.addresses) == 4 and added.address == joining

    owners_after = _owners(cluster, list(full.values()))
    moved = [k for k in keys if owners_after[full[k]] != owners_before[full[k]]]
    kept = [k for k in keys if k not in moved]
    # consistent hashing re-homes ~1/4 of the space: some but never all
    assert len(moved) == N_KEYS // 4
    # a joining node only GAINS keys: everything that moved, moved to it
    assert all(owners_after[full[k]] == added.address for k in moved)

    for k in moved:
        holders = _holder_addresses(cluster, full[k])
        assert holders == [added.address], \
            f"moved key {k} should live ONLY on the new node, found {holders}"
    for k in kept:
        holders = _holder_addresses(cluster, full[k])
        assert holders == [owners_before[full[k]]], \
            f"unmoved key {k} changed holders: {holders}"
        assert _slot_of(cluster, owners_before[full[k]], full[k]) == \
            slot_before[k], f"unmoved key {k} changed slot"

    # migrated state survived: 3 hits before the move + 1 now, SAME window
    async def verify_hits():
        client = AsyncClient(cluster.get_peer())
        for k in keys:
            r = (await client.get_rate_limits([req(k)]))[0]
            assert r.error == "", k
            assert r.status == Status.UNDER_LIMIT, k
            assert r.remaining == LIMIT - 4, \
                f"{k}: remaining {r.remaining} (hits lost in migration)"
            assert r.reset_time == reset_time[k], \
                f"{k}: reset_time changed across migration"
        await client.close()
    run(loop, verify_hits())

    # GLOBAL keys: re-homed ones re-registered on the new owner (config
    # AND state shipped), and the sources keep serving their replicas.
    # Migration is compared against the PRE-change replica states, not an
    # idealized hit count: the async global forward path may still be
    # reconciling when the ring changes, and migration's contract is to
    # move what exists, not to finish the sync protocol.
    gmoved = [k for k in gkeys
              if _owners(cluster, [gfull[k]])[gfull[k]] == added.address]
    assert len(gmoved) == N_GLOBAL // 4, gmoved
    new_gkeys = set(added.instance.engine.global_keys())
    for k in gmoved:
        assert gfull[k] in new_gkeys, \
            f"GLOBAL {k} not re-registered on its new owner"
    for node in cluster.nodes[:-1]:
        assert set(node.instance.engine.global_keys()), \
            "source node dropped its GLOBAL replicas"
    for k in gmoved:
        cands = gstate_before.get(k)
        if not cands:
            continue  # key never finished registering anywhere pre-change
        got = added.instance.engine.export_global_rows([gfull[k]])[0]
        assert (got["remaining"], got["expire"], got["cfg_limit"]) in cands, \
            f"GLOBAL {k} state did not survive the move: {got} != {cands}"

    # ---- shrink back: the departing node ships everything it owns -------
    ghost = added.address
    run(loop, cluster.remove_instance(len(cluster.nodes) - 1))
    assert len(cluster.addresses) == 3 and ghost not in cluster.addresses

    owners_final = _owners(cluster, list(full.values()))
    for k in moved:
        # back on a surviving node, state intact: 4 hits so far + 1 now
        holders = _holder_addresses(cluster, full[k])
        assert holders == [owners_final[full[k]]], k

    async def verify_shrink():
        client = AsyncClient(cluster.get_peer())
        for k in keys:
            r = (await client.get_rate_limits([req(k)]))[0]
            assert r.error == "", k
            assert r.remaining == LIMIT - 5, \
                f"{k}: remaining {r.remaining} after shrink"
            assert r.reset_time == reset_time[k], k
        await client.close()
    run(loop, verify_shrink())

    # migration counters moved through the metrics surface
    total_out = sum(_counter(n.instance, "guber_tpu_migrated_keys_total",
                             {"direction": "out"}) for n in cluster.nodes)
    assert total_out >= len(moved)


def _counter(instance, name, labels):
    for fam in instance.metrics.registry.collect():
        for sample in fam.samples:
            if sample.name == name and all(
                    sample.labels.get(k) == v for k, v in labels.items()):
                return sample.value
    return 0.0
