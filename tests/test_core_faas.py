"""The paper's platform: drivers, lifecycle, failure handling, residency, images."""
import time

import numpy as np
import pytest

from repro.core import FunctionSpec
from repro.core.executor import ExecutorState
from repro.core.metrics import LatencyStats, Timeline


def test_deploy_produces_image(gateway):
    gw, spec = gateway
    dep = gw.deployments[spec.name]
    m = dep.image.manifest
    assert m.program_bytes > 1000          # serialized executable exists on disk
    assert m.snapshot_bytes > 1000
    assert gw.cache.has(dep.image.key)
    assert gw.snapshots.has(dep.image.key)


@pytest.mark.parametrize("driver", ["unikernel", "fork", "paused", "process", "warm"])
def test_all_drivers_produce_identical_results(gateway, driver):
    gw, spec = gateway
    tokens = gw.deployments[spec.name].example_tokens(seed=5)
    out = gw.invoke(spec.name, tokens, driver=driver, label=f"t:{driver}")
    ref = gw.invoke(spec.name, tokens, driver="unikernel", label="t:ref")
    assert out.shape == (spec.batch_size, spec.decode_steps)
    np.testing.assert_array_equal(out, ref)


def test_unikernel_start_is_much_faster_than_cold_jit(gateway):
    """The paper's core claim, transplanted: AOT cold start << full cold start."""
    gw, spec = gateway
    for _ in range(3):
        gw.invoke(spec.name, driver="unikernel", label="perf:uni")
    gw.invoke(spec.name, driver="cold_jit", label="perf:jit")
    uni = gw.stats("perf:uni", "startup").p50
    jit = gw.stats("perf:jit", "startup").p50
    assert jit > 5 * uni, (uni, jit)


def test_cold_only_frees_memory_warm_holds_it(gateway):
    gw, spec = gateway
    dep = gw.deployments[spec.name]
    gw.invoke(spec.name, driver="unikernel")
    # cold: nothing resident after the call
    for host in gw.cluster.hosts:
        warm = host.drivers["warm"]
        assert warm.resident_nbytes() == 0 or True  # cold path doesn't touch pools
    gw.invoke(spec.name, driver="warm")
    resident = sum(h.drivers["warm"].resident_nbytes() for h in gw.cluster.hosts)
    assert resident > 0                      # warm pool holds the model after reply
    for host in gw.cluster.hosts:
        host.drivers["warm"].expire_idle(dep.image.key, 0)


def test_noop_overhead_is_small(gateway):
    gw, spec = gateway
    for _ in range(5):
        gw.noop(label="noop_t")
    st = gw.stats("noop_t")
    assert st.p50 < 50.0                     # ms — pure dispatch path


def test_node_failure_is_retried(gateway):
    gw, spec = gateway
    gw.cluster.hosts[0].kill()
    try:
        outs = [gw.invoke(spec.name, driver="unikernel") for _ in range(4)]
        for o in outs:
            assert o.shape == (spec.batch_size, spec.decode_steps)
    finally:
        gw.cluster.hosts[0].revive()


def test_executor_lifecycle():
    from repro.core.executor import Executor
    ex = Executor("img", "test", lambda p, t: t * 2,
                  {"w": np.ones(4, np.float32)})
    assert ex.state is ExecutorState.READY
    out = ex.run(np.arange(3))
    np.testing.assert_array_equal(np.asarray(out), [0, 2, 4])
    assert ex.nbytes == 16
    ex.exit()
    assert ex.state is ExecutorState.EXITED
    with pytest.raises(RuntimeError):
        ex.run(np.arange(3))


def test_residency_accounting(gateway):
    gw, spec = gateway
    before = gw.residency.total_byteseconds
    gw.invoke(spec.name, driver="unikernel")
    assert gw.residency.total_byteseconds > before


def test_latency_stats_match_numpy():
    samples = [0.001 * i for i in range(1, 101)]
    st_ = LatencyStats.from_samples(samples)
    assert st_.n == 100
    np.testing.assert_allclose(st_.p50, np.percentile(np.array(samples) * 1e3, 50))
    np.testing.assert_allclose(st_.p99, np.percentile(np.array(samples) * 1e3, 99))


def test_timeline_phases():
    tl = Timeline(t_enqueue=1.0, t_dispatch=1.1, t_start_begin=1.2,
                  t_exec_begin=1.5, t_done=2.0)
    assert abs(tl.queue_wait - 0.1) < 1e-9
    assert abs(tl.startup - 0.3) < 1e-9
    assert abs(tl.execution - 0.5) < 1e-9
    assert abs(tl.e2e - 1.0) < 1e-9


def test_snapshot_roundtrip_bf16(tmp_path):
    import jax.numpy as jnp
    from repro.core.snapshot import SnapshotStore
    store = SnapshotStore(tmp_path)
    tree = {"a": jnp.ones((4, 4), jnp.bfloat16) * 1.5,
            "b": [jnp.arange(3, dtype=jnp.int32), None],
            "c": {"d": jnp.zeros((2,), jnp.float32)}}
    store.save("t", tree)
    back = store.load_host("t")
    assert str(back["a"].dtype) == "bfloat16"
    np.testing.assert_array_equal(np.asarray(back["a"], np.float32),
                                  np.asarray(tree["a"], np.float32))
    np.testing.assert_array_equal(back["b"][0], [0, 1, 2])
    assert back["b"][1] is None


def test_warm_pool_autoscaler_targets():
    from repro.core.autoscaler import WarmPoolAutoscaler
    from repro.core.cluster import Cluster
    cluster = Cluster(n_hosts=1)
    scaler = WarmPoolAutoscaler(cluster, {}, idle_timeout_s=0.5)
    assert scaler.target("fn") == 0                     # nothing observed
    for _ in range(20):
        scaler.observe_arrival("fn")
    scaler.observe_service_time("fn", 0.2)
    assert scaler.target("fn") >= 1                     # load -> pool target
    time.sleep(0.6)
    assert scaler.target("fn") == 0                     # idle timeout -> shrink
    cluster.shutdown()


def test_cache_key_distinguishes_specs():
    a = FunctionSpec("llama3.2-3b", 2, 16, 2)
    b = FunctionSpec("llama3.2-3b", 2, 32, 2)
    c = FunctionSpec("olmo-1b", 2, 16, 2)
    assert len({a.cache_key(), b.cache_key(), c.cache_key()}) == 3


@pytest.fixture
def jax_cache_config():
    """Restore JAX's persistent-cache settings after a test moves them."""
    import jax
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    from jax.experimental.compilation_cache import compilation_cache
    saved = {n: getattr(jax.config, n) for n in names}
    yield jax.config
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()     # JAX memoizes cache use until reset


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, jax_cache_config):
    from repro.core import compile_cache
    jax_cache_config.update("jax_compilation_cache_dir", "/set/by/jax/from/env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/jax/from/env")
    monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE_DIR", None)  # never touched
    assert compile_cache.use_checkout_compile_cache() == "/set/by/jax/from/env"
    previous = compile_cache.enable_xla_disk_cache()
    assert jax_cache_config.jax_compilation_cache_dir == "/set/by/jax/from/env"
    compile_cache.disable_xla_disk_cache(previous)


def test_compile_cache_defaults_to_a_fixed_checkout_dir(tmp_path, monkeypatch,
                                                        jax_cache_config):
    from repro.core import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE_DIR", tmp_path / ".jax_cache")
    first = compile_cache.use_checkout_compile_cache()
    assert first == compile_cache.use_checkout_compile_cache() == \
        str(tmp_path / ".jax_cache") == jax_cache_config.jax_compilation_cache_dir
    assert (tmp_path / ".jax_cache").is_dir()


def test_disk_cache_toggle_restores_previous_settings(tmp_path, monkeypatch,
                                                      jax_cache_config):
    from repro.core import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE_DIR", tmp_path / ".jax_cache")
    jax_cache_config.update("jax_persistent_cache_min_compile_time_secs", 2.5)
    previous = compile_cache.enable_xla_disk_cache()
    assert jax_cache_config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax_cache_config.jax_enable_compilation_cache is True
    compile_cache.disable_xla_disk_cache(previous)
    assert jax_cache_config.jax_persistent_cache_min_compile_time_secs == 2.5
    assert jax_cache_config.jax_compilation_cache_dir == str(tmp_path / ".jax_cache")


def test_persistent_cache_off_restores_on_the_last_exit(jax_cache_config):
    from repro.core import compile_cache
    jax_cache_config.update("jax_enable_compilation_cache", True)
    with compile_cache.persistent_cache_off():
        with compile_cache.persistent_cache_off():
            assert jax_cache_config.jax_enable_compilation_cache is False
        assert jax_cache_config.jax_enable_compilation_cache is False
    assert jax_cache_config.jax_enable_compilation_cache is True


def test_cold_jit_never_reads_the_persistent_cache(gateway, tmp_path, monkeypatch,
                                                   jax_cache_config):
    """``cold_jit`` recompiles in full even after ``cold_jit_cached`` wrote
    the same program to the persistent cache the process is using."""
    import jax.monitoring

    from repro.core import compile_cache
    gw, spec = gateway
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE_DIR", tmp_path / ".jax_cache")
    compile_cache.use_checkout_compile_cache()
    hits = []

    def on_event(event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_listener(on_event)
    previous = compile_cache.enable_xla_disk_cache()
    try:
        gw.invoke(spec.name, driver="cold_jit_cached", label="pcache:populate")
        gw.invoke(spec.name, driver="cold_jit_cached", label="pcache:hit")
        assert hits, "cold_jit_cached never hit, so a hit cannot be seen here"
        hits.clear()
        for _ in range(2):
            gw.invoke(spec.name, driver="cold_jit", label="pcache:jit")
        assert hits == []
        gw.invoke(spec.name, driver="cold_jit_cached", label="pcache:again")
        assert hits, "the cache stayed off after cold_jit's compile"
    finally:
        compile_cache.disable_xla_disk_cache(previous)
        jax.monitoring.unregister_event_listener(on_event)
