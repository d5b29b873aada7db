"""Fault-tolerance runtime: straggler detection, retry, elastic policy."""

import signal

import pytest

from repro.runtime import (ElasticController, PreemptionHandler,
                           StragglerMonitor, retry)


def test_preemption_programmatic():
    prm = PreemptionHandler()
    assert not prm.should_stop
    prm.request_stop()
    assert prm.should_stop


def test_preemption_signal():
    prm = PreemptionHandler(install=True, signals=(signal.SIGUSR1,))
    assert not prm.should_stop
    signal.raise_signal(signal.SIGUSR1)
    assert prm.should_stop


def test_straggler_detection():
    mon = StragglerMonitor(n_hosts=4, threshold=2.0, patience=3)
    normal = [1.0, 1.0, 1.0, 1.0]
    slow = [1.0, 1.0, 1.0, 5.0]
    for _ in range(2):
        mon.record(slow)
    assert mon.stragglers() == []          # not patient enough yet
    mon.record(slow)
    assert mon.stragglers() == [3]
    mon.record(normal)                     # recovery clears the streak
    assert mon.stragglers() == []


def test_straggler_needs_consistency():
    mon = StragglerMonitor(n_hosts=3, threshold=2.0, patience=2)
    mon.record([1.0, 1.0, 9.0])
    mon.record([1.0, 9.0, 1.0])            # different host each time
    assert mon.stragglers() == []


def test_retry_succeeds_after_failures():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert retry(flaky, max_attempts=5, sleep=lambda s: None) == "ok"
    assert calls["n"] == 3


def test_retry_exhausts():
    def always():
        raise OSError("down")
    with pytest.raises(OSError):
        retry(always, max_attempts=2, sleep=lambda s: None)


def test_retry_does_not_catch_other_exceptions():
    def typo():
        raise ValueError("bug")
    with pytest.raises(ValueError):
        retry(typo, max_attempts=3, sleep=lambda s: None)


def test_elastic_controller():
    ec = ElasticController(model_parallel=16)
    plan = ec.plan_mesh(healthy_chips=256)
    assert plan == {"data": 16, "model": 16}
    # lose a host worth of chips -> shrink DP
    plan = ec.plan_mesh(healthy_chips=240)
    assert plan == {"data": 15, "model": 16}
    assert ec.should_rescale(current_dp=16, healthy_chips=240)
    assert not ec.should_rescale(current_dp=15, healthy_chips=240)
    with pytest.raises(RuntimeError):
        ec.plan_mesh(healthy_chips=8)


def test_compile_cache_location_and_device_line(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache is one fixed directory in the checkout, used on a TPU
    only; the launchers' device line says whether Pallas is interpreted."""
    import os

    import jax

    from repro.core import QuantPolicy
    from repro.launch.device import (CACHE_DIR, device_summary,
                                     enable_compile_cache)
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert enable_compile_cache() is None            # this suite runs on CPU
    assert jax.config.jax_compilation_cache_dir == before
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert CACHE_DIR == os.path.join(root, ".jax_cache")
    line = device_summary(QuantPolicy.fqt("ptq", 8, backend="pallas"))
    assert line.startswith("platform=cpu ") and "pallas=interpreted" in line
    assert "pallas" not in device_summary(QuantPolicy.qat())
