"""The trace reduction: busy/idle as a union of device-op intervals,
collective time not overlapped by compute, top ops, and idle gaps
attributed to the benchmark's host spans."""

import os
from types import SimpleNamespace as NS

import pytest

from bench.lib import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.xplane.pb")


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * 1e6, duration_ns=dur_ms * 1e6)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def recorded_planes():
    """A hand-made trace of two chips, 100 ms long: chip 0 computes in
    [0,20] and [20,30] (overlapping ops), all-reduces in [25,40], idles
    [40,70] while the host ticks, computes [70,100]; chip 1 is busy
    throughout. One jitted program runs twice on each chip."""
    host = plane("/host:CPU", python=[ev("bench.window", 0, 100),
                                      ev("bench.tick", 38, 35),
                                      ev("bench.job", 0, 100),
                                      ev("other", 40, 30)])
    d0 = plane("/device:TPU:0",
               XLA_Ops=[ev("fusion.1", 0, 20), ev("fusion.2", 10, 20),
                        ev("all-reduce.3", 25, 15), ev("fusion.1", 70, 30)],
               XLA_Modules=[ev("jit_serve_step(7)", 0, 40),
                            ev("jit_serve_step(7)", 70, 30)])
    d1 = plane("/device:TPU:1",
               XLA_Ops=[ev("fusion.1", 0, 100)],
               XLA_Modules=[ev("jit_serve_step(7)", 0, 50),
                            ev("jit_serve_step(7)", 50, 50)])
    return [host, d0, d1, plane("/host:metadata")]


def test_interval_math():
    m = trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert m == [(0, 3), (5, 8)]
    assert trace.total(m) == 6
    assert trace.gaps(m, 0, 10) == [(3, 5), (8, 10)]
    assert trace.overlap(m, [(2, 6)]) == 2


def test_busy_idle_collectives_modules_and_gaps():
    tf = trace.reduce_planes(recorded_planes())
    assert tf.devices == 2
    assert tf.window_s == pytest.approx(0.1)
    # chip 0 busy 40 + 30 = 70 ms, chip 1 100 ms: mean 85 ms
    assert tf.busy_s == pytest.approx(0.085)
    assert tf.idle_share == pytest.approx(0.15)
    # chip 0's all-reduce [25,40] overlaps compute until 30: 10 ms exposed
    assert tf.collective_s == pytest.approx(0.0075)
    assert tf.collective_exposed_s == pytest.approx(0.005)
    assert tf.module("serve_step") == (pytest.approx(2.0), pytest.approx(0.085))
    assert tf.top_ops[0][0] == "fusion.1"
    assert tf.top_ops[0][1] == pytest.approx((0.05 + 0.1) / 2)
    # chip 0's one gap, [40,70], lies mostly inside the tick span
    assert tf.idle_gaps == [["bench.tick", pytest.approx(0.03)]]


def test_no_device_plane_reads_nothing():
    assert trace.reduce_planes([plane("/host:CPU", python=[])]) is None


def test_recorded_chip_trace():
    """A trace recorded on one v5e: three steps of a small matmul program
    with the benchmark's spans between them."""
    from jax.profiler import ProfileData

    tf = trace.reduce_planes(ProfileData.from_file(DATA).planes)
    assert tf is not None and tf.devices == 1
    assert 0 < tf.busy_s < tf.window_s
    assert tf.top_ops and all(s > 0 for _, s in tf.top_ops)
    assert any(label.startswith("bench.") for label, _ in tf.idle_gaps)
