"""The trace reduction on a hand-built profile: busy union, idle share,
per-operation time and idle gaps labelled by the benchmark's host spans."""

import types

import pytest

from bench import trace


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=evs) for ln, evs in lines])


def _profile():
    host = _plane("/host:CPU", [("python", [
        _ev("bench.trace_window", 1000, 10000),
        _ev("bench.engine_step", 1000, 4000),
        _ev("bench.wait_arrival", 5000, 3000),
        _ev("unrelated", 0, 20000)])])
    dev = _plane("/device:TPU:0", [
        ("XLA Ops", [_ev("fusion.1", 500, 1500, tf_op="jit(f)/q[mlp|fwd]/x"),
                     _ev("fusion.1", 2500, 500),
                     _ev("custom-call.7", 2800, 1200),
                     _ev("fusion.2", 9000, 3000)]),
        ("XLA Modules", [_ev("jit_f", 0, 20000)])])
    return types.SimpleNamespace(planes=[host, dev])


def test_reduce_profile_fixed_numbers():
    r = trace.reduce_profile(_profile())
    # window [1000, 11000): ops clipped to [1000,2000) [2500,3000)
    # [2800,4000) [9000,11000) -> union 1000 + 1500 + 2000 = 4500 ns
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(4500e-9)
    assert r["devices"] == 1
    assert r["ops"]["fusion.1"]["count"] == 2
    assert r["ops"]["fusion.1"]["seconds"] == pytest.approx(1500e-9)
    assert r["ops"]["fusion.1"]["scope"] == "jit(f)/q[mlp|fwd]/x"
    assert r["ops"]["fusion.2"]["seconds"] == pytest.approx(2000e-9)
    # gaps: [4000, 9000) under wait_arrival at its middle, [2000, 2500)
    assert r["gaps"][0] == ["bench.wait_arrival", pytest.approx(5000e-9)]
    assert r["gaps"][1] == ["bench.engine_step", pytest.approx(500e-9)]
    assert trace.top_ops(r, 2) == [["fusion.2", pytest.approx(2e-6)],
                                   ["fusion.1", pytest.approx(1.5e-6)]]


def test_reduce_profile_needs_the_window_span():
    pd = _profile()
    pd.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.trace_window"):
        trace.reduce_profile(pd)
