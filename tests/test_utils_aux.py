"""Aux-subsystem utilities (SURVEY §5): timer, profiling hooks,
topology/capability probe (the hwid parse analog), debug logging."""
import os

import pytest


def test_timer_shape():
    import time

    from accl_tpu.utils.timing import Timer

    t = Timer()
    t.start()
    time.sleep(0.01)
    t.end()
    us = t.durationUs()
    assert 5_000 <= us <= 5_000_000
    assert abs(t.duration_ns() - us * 1000) < 1e3
    with Timer() as t2:
        time.sleep(0.002)
    assert t2.durationUs() >= 1_000


def test_profiling_timed_and_time_fn():
    import jax.numpy as jnp

    from accl_tpu.utils.profiling import time_fn, timed

    results = {}
    with timed("block", results):
        sum(range(1000))
    assert len(results["block"]) == 1 and results["block"][0] > 0

    import jax

    f = jax.jit(lambda x: x * 2 + 1)
    dt = time_fn(f, jnp.ones(128), iters=3, warmup=1)
    assert dt > 0


def test_topology_probe_and_hwid():
    from accl_tpu.utils.topology import dump, probe

    cap = probe()
    assert cap.num_devices == 8  # conftest's virtual CPU mesh
    word = cap.hwid()
    # bit layout: platform (cpu=0), arith bit 4, compression bit 5,
    # remote-dma bit 6, device count at bits 8+
    assert word & 0xF == 0
    assert (word >> 4) & 1 == 1
    assert (word >> 5) & 1 == 1
    assert (word >> 8) & 0xFFFF == 8
    text = dump()
    assert "platform=cpu" in text and "n=8" in text


def test_debug_logging_env(capsys, monkeypatch):
    import importlib
    import logging as stdlog

    from accl_tpu.utils import logging as alog

    monkeypatch.setenv("ACCL_DEBUG", "1")
    # reset the module's one-shot configuration so the env is honored
    importlib.reload(alog)
    stdlog.getLogger("accl_tpu").handlers.clear()
    log = alog.get_logger(rank=3)
    log.debug("hello-debug")
    err = capsys.readouterr().err
    # structured rank prefix: "[accl r3] D hello-debug"
    assert "hello-debug" in err and "[accl r3]" in err
    # restore: unconfigured module state for later tests
    monkeypatch.delenv("ACCL_DEBUG")
    stdlog.getLogger("accl_tpu").handlers.clear()
    importlib.reload(alog)


def test_accl_log_level_env(capsys, monkeypatch):
    import importlib
    import logging as stdlog

    from accl_tpu.utils import logging as alog

    monkeypatch.setenv("ACCL_LOG", "info")
    importlib.reload(alog)
    stdlog.getLogger("accl_tpu").handlers.clear()
    log = alog.get_logger(rank=1)
    log.info("at-info")
    log.debug("below-level")
    err = capsys.readouterr().err
    assert "[accl r1] I at-info" in err
    assert "below-level" not in err
    monkeypatch.delenv("ACCL_LOG")
    stdlog.getLogger("accl_tpu").handlers.clear()
    importlib.reload(alog)


def test_initialize_multihost_arg_assembly(monkeypatch):
    # dry_run resolves explicit args + ACCL_* env defaults without
    # touching jax (a second host doesn't exist on CI); explicit
    # arguments win over the environment
    from accl_tpu.utils.bringup import initialize_multihost

    monkeypatch.setenv("ACCL_COORDINATOR", "10.0.0.1:8476")
    monkeypatch.setenv("ACCL_NUM_PROCESSES", "4")
    monkeypatch.setenv("ACCL_PROCESS_ID", "2")
    kw = initialize_multihost(dry_run=True)
    assert kw == {"coordinator_address": "10.0.0.1:8476",
                  "num_processes": 4, "process_id": 2}

    kw = initialize_multihost(coordinator_address="h:1", process_id=0,
                              local_device_ids=[0, 1], dry_run=True)
    assert kw["coordinator_address"] == "h:1"
    assert kw["process_id"] == 0
    assert kw["local_device_ids"] == [0, 1]
    assert kw["num_processes"] == 4  # env still fills the gap

    monkeypatch.delenv("ACCL_COORDINATOR")
    monkeypatch.delenv("ACCL_NUM_PROCESSES")
    monkeypatch.delenv("ACCL_PROCESS_ID")
    assert initialize_multihost(dry_run=True) == {}  # pod auto-detect


@pytest.fixture
def _restore_jax_cache_config():
    # enable() mutates process-global jax config; leaking it would make
    # every later compile in the suite silently persist to a test dir
    import jax

    keys = ("jax_persistent_cache_min_compile_time_secs",
            "jax_compilation_cache_dir")
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_compile_cache_env_dir_gets_entries(tmp_path, monkeypatch,
                                            _restore_jax_cache_config):
    # JAX_COMPILATION_CACHE_DIR, where set, is the cache: a compile
    # after enable() lands an entry there
    import jax
    import jax.numpy as jnp

    from accl_tpu.utils.compile_cache import enable

    target = str(tmp_path / "envcache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
    assert enable() == target
    assert jax.config.jax_compilation_cache_dir == target
    fn = jax.jit(lambda x: x * 2 + 1)
    fn(jnp.ones((8, 128))).block_until_ready()
    assert os.listdir(target), "no cache entry written for a fresh compile"


def test_compile_cache_default_is_fixed_in_checkout(
        monkeypatch, _restore_jax_cache_config):
    # unset, the cache sits at one path inside the checkout, the same
    # on every call (the path is part of a cache entry's key)
    import jax

    from accl_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable() == want
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_compile_cache_default_dir_is_gitignored():
    # cache entries are made at run time and never committed
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
