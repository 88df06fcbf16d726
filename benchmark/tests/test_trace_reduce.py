"""The reduction from a profiler trace to busy time, per-op time and
attributed idle gaps, pinned on a small trace recorded on the CPU and on
hand-made planes."""

import time
from types import SimpleNamespace as NS

import pytest

from benchmark import trace_reduce


def _ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats))


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=evs) for n, evs in lines])


def test_reduce_hand_made_planes():
    host = _plane("/host:CPU", [("main", [
        _ev("bench/window", 1000, 10_000),
        _ev("bench/decode", 1000, 3000),
        _ev("bench/bus_submit", 4000, 7000),
        _ev("unrelated", 0, 50_000),
    ])])
    dev = _plane("/device:TPU:0", [
        ("XLA Ops", [
            _ev("fusion.1", 500, 1500),      # clipped to [1000, 2000]
            _ev("ladder_kernel", 5000, 2000),
            _ev("fusion.1", 6000, 2000),     # overlaps the ladder
            _ev("fusion.2", 10_500, 1000),   # clipped to [10500, 11000]
        ]),
        ("XLA Modules", [_ev("jit_verify", 0, 100_000)]),
    ])
    r = trace_reduce.reduce([host, dev])
    assert r["window_s"] == pytest.approx(10e-6)
    # union: [1000,2000] + [5000,8000] + [10500,11000] = 4500 ns
    assert r["busy_s"] == pytest.approx(4.5e-6)
    assert r["ops"]["fusion.1"] == pytest.approx(3e-6)
    assert r["ops"]["ladder_kernel"] == pytest.approx(2e-6)
    assert r["ops"]["fusion.2"] == pytest.approx(0.5e-6)
    # gaps: [2000,5000] (decode 2000 ns, bus_submit 1000) and
    # [8000,10500] (bus_submit)
    assert r["idle_gaps"] == [
        ["bench/decode", pytest.approx(3e-6)],
        ["bench/bus_submit", pytest.approx(2.5e-6)],
    ]
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(3e-6)]


def test_reduce_cpu_trace(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/handler"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench/idle"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    r = trace_reduce.reduce_dir(str(tmp_path))
    assert r["n_devices"] == 1
    assert 0.06 <= r["window_s"] < 5.0
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert any("dot" in name for name in r["ops"])
    # the three sleeps are the longest idle stretches
    assert [g[0] for g in r["idle_gaps"][:3]] == ["bench/idle"] * 3
    assert all(g[1] >= 0.015 for g in r["idle_gaps"][:3])
