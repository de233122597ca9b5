"""Config parsing + daemon composition tests (reference
cmd/gubernator/config.go:59-147, main.go:40-140)."""

import asyncio
import os

import pytest

import gubernator_tpu  # noqa: F401
from gubernator_tpu.config import config_from_env, load_env_file


@pytest.fixture
def clean_env(monkeypatch):
    for k in list(os.environ):
        if k.startswith("GUBER_"):
            monkeypatch.delenv(k)
    return monkeypatch


def test_defaults(clean_env):
    c = config_from_env()
    assert c.grpc_listen_address == "localhost:81"
    assert c.http_listen_address == "localhost:80"
    assert c.advertise_address == "localhost:81"
    assert c.cache_size == 50000
    assert c.behaviors.batch_wait == 0.0005
    assert c.behaviors.batch_limit == 1000


def test_env_overrides(clean_env):
    clean_env.setenv("GUBER_GRPC_ADDRESS", "0.0.0.0:9999")
    clean_env.setenv("GUBER_BATCH_LIMIT", "500")
    clean_env.setenv("GUBER_ETCD_ENDPOINTS", "http://e1:2379,http://e2:2379")
    c = config_from_env()
    assert c.grpc_listen_address == "0.0.0.0:9999"
    assert c.advertise_address == "0.0.0.0:9999"  # falls back to grpc addr
    assert c.behaviors.batch_limit == 500
    assert c.etcd_addresses == ["http://e1:2379", "http://e2:2379"]
    assert c.etcd_enabled


def test_k8s_etcd_exclusive(clean_env):
    clean_env.setenv("GUBER_ETCD_ENDPOINTS", "http://e1:2379")
    clean_env.setenv("GUBER_K8S_NAMESPACE", "default")
    with pytest.raises(ValueError):
        config_from_env()


def test_batch_limit_cap(clean_env):
    clean_env.setenv("GUBER_BATCH_LIMIT", "5000")
    with pytest.raises(ValueError):
        config_from_env()


def test_env_file(clean_env, tmp_path):
    f = tmp_path / "test.conf"
    f.write_text(
        "# comment line\n"
        "\n"
        "GUBER_GRPC_ADDRESS=h:1\n"
        "GUBER_CACHE_SIZE = 12345\n"
    )
    c = config_from_env(str(f))
    assert c.grpc_listen_address == "h:1"
    assert c.cache_size == 12345


def test_env_file_malformed(clean_env, tmp_path):
    f = tmp_path / "bad.conf"
    f.write_text("NOT A KEY VALUE LINE\n")
    with pytest.raises(ValueError, match="line '1'"):
        load_env_file(str(f))


@pytest.mark.slow
def test_daemon_end_to_end(clean_env):
    """Boot the full daemon (static discovery), drive gRPC + HTTP surfaces."""
    from gubernator_tpu.daemon import Daemon

    clean_env.setenv("GUBER_GRPC_ADDRESS", "127.0.0.1:0")
    clean_env.setenv("GUBER_HTTP_ADDRESS", "127.0.0.1:18980")
    clean_env.setenv("GUBER_TPU_CAPACITY_PER_SHARD", "1024")
    clean_env.setenv("GUBER_TPU_BATCH_PER_SHARD", "128")

    async def body():
        conf = config_from_env()
        d = Daemon(conf)
        await d.start()
        try:
            from gubernator_tpu.api.types import RateLimitReq, Second, Status
            from gubernator_tpu.client import AsyncClient
            import aiohttp

            client = AsyncClient(d.grpc.address)
            rs = await client.get_rate_limits([RateLimitReq(
                name="daemon_e2e", unique_key="k", hits=1, limit=2,
                duration=Second)])
            assert rs[0].remaining == 1
            h = await client.health_check()
            assert h.status == "healthy"
            await client.close()

            async with aiohttp.ClientSession() as s:
                async with s.get("http://127.0.0.1:18980/v1/HealthCheck") as r:
                    assert (await r.json())["status"] == "healthy"
                async with s.get("http://127.0.0.1:18980/metrics") as r:
                    assert "grpc_request_counts" in (await r.text())
        finally:
            await d.stop()

    asyncio.new_event_loop().run_until_complete(body())


def test_lockstep_stack_env(clean_env):
    clean_env.setenv("GUBER_LOCKSTEP_STACK", "4")
    c = config_from_env()
    assert c.behaviors.lockstep_stack == 4


def test_lockstep_stack_invalid(clean_env):
    clean_env.setenv("GUBER_LOCKSTEP_STACK", "0")
    with pytest.raises(ValueError):
        config_from_env()


def test_exact_keys_engine_plumb(clean_env):
    """EngineConfig.exact_keys reaches the native router (storage arrays
    allocated; behavior covered by the differential in
    test_native_router.py)."""
    from gubernator_tpu import native
    if not native.available():
        pytest.skip("native router unavailable")
    from gubernator_tpu.core.engine import RateLimitEngine
    eng = RateLimitEngine(capacity_per_shard=32, batch_per_shard=8,
                          global_capacity=8, global_batch_per_shard=4,
                          max_global_updates=4, exact_keys=True)
    assert eng.native is not None
    from gubernator_tpu.api.types import RateLimitReq
    r = eng.process([RateLimitReq(name="x", unique_key="k", hits=1,
                                  limit=5, duration=1000)], now=1)[0]
    assert r.remaining == 4


def test_replay_cap_env(clean_env):
    """GUBER_REPLAY_CAP reaches both the daemon config and the engine
    (env wins over the param, mirroring GUBER_EXACT_KEYS)."""
    clean_env.setenv("GUBER_REPLAY_CAP", "7")
    c = config_from_env()
    assert c.engine.replay_cap == 7
    from gubernator_tpu.core.engine import RateLimitEngine
    eng = RateLimitEngine(capacity_per_shard=32, batch_per_shard=8,
                          global_capacity=8, global_batch_per_shard=4,
                          max_global_updates=4, replay_cap=99)
    assert eng.replay_cap == 7  # env overrides the param


def test_replay_cap_default(clean_env):
    from gubernator_tpu.core.engine import RateLimitEngine
    eng = RateLimitEngine(capacity_per_shard=32, batch_per_shard=8,
                          global_capacity=8, global_batch_per_shard=4,
                          max_global_updates=4)
    assert eng.replay_cap == 128


def test_place_compile_cache_one_place(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code.  Unset: the
    cache is <checkout>/.jax_cache, derived from the package's own path."""
    import jax

    from gubernator_tpu import config

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert config.place_compile_cache() == str(tmp_path)
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(config.__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert config.place_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
