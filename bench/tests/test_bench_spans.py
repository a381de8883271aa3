"""The serving engine's spans, reduced: device idle time put down to the
innermost engine span, self times, the longest steps and the per-layer
numbers, and a rehearsal of the tool that hands the engine a recorder."""

import json

import pytest

import rehearse
from test_bench_trace import ev, plane

from bench.lib import engine_spans, trace
from repro.spans import Record


def nested_planes():
    """One chip, a 100 ms window. It computes in [0,10], [30,50] and
    [80,95] ms. The host runs two engine steps with nested spans, and one
    stretch of idle falls between them, outside the engine."""
    host = plane("/host:CPU", python=[
        ev("bench.window", 0, 100), ev("bench.engine_step", 4, 57),
        ev("serve.step", 5, 55), ev("serve.prefill", 8, 20),
        ev("serve.sync", 20, 8), ev("serve.decode", 28, 7),
        ev("serve.sync", 35, 17), ev("host.gc", 52, 4),
        ev("serve.step", 70, 20), ev("serve.decode", 70, 5),
        ev("host.gc", 75, 3), ev("other", 60, 40)])
    d0 = plane("/device:TPU:0",
               XLA_Ops=[ev("fusion.1", 0, 10), ev("fusion.2", 30, 20),
                        ev("fusion.1", 80, 15)],
               XLA_Modules=[ev("jit_serve_step(1)", 0, 95)])
    return [host, d0]


def test_idle_goes_to_the_innermost_engine_span():
    idle = engine_spans.idle_by_span(nested_planes())
    ms = {k: pytest.approx(v * 1e-3) for k, v in {
        "serve.prefill": 10, "serve.sync": 10, "serve.decode": 7,
        "host.gc": 7, "serve.step": 6, engine_spans.OUTSIDE: 15}.items()}
    assert idle["by_span"] == ms
    tf = trace.reduce_planes(nested_planes())
    assert idle["window_s"] == pytest.approx(tf.window_s)
    assert idle["idle_s"] == pytest.approx(tf.idle_share * tf.window_s)
    assert sum(idle["by_span"].values()) == pytest.approx(idle["idle_s"])
    # [10,30], [50,60] and [70,80] lie inside the two steps
    assert idle["in_step_s"] == pytest.approx(0.040)
    line = engine_spans.summary([], 0, 1, {}, idle)
    assert line["metrics"] == {"engine_idle_share.serve": pytest.approx(40.0)}


def test_recorded_chip_trace_has_no_engine_span():
    from jax.profiler import ProfileData

    from test_bench_trace import DATA

    planes = list(ProfileData.from_file(DATA).planes)
    idle, tf = engine_spans.idle_by_span(planes), trace.reduce_planes(planes)
    assert idle["window_s"] == pytest.approx(tf.window_s)
    assert idle["by_span"] == {engine_spans.OUTSIDE: pytest.approx(
        tf.idle_share * tf.window_s)}
    assert idle["in_step_s"] == 0


def test_no_device_plane_reads_nothing():
    assert engine_spans.idle_by_span([plane("/host:CPU", python=[])]) is None


def records():
    """Two steps, each with a prefill and a decode, waits before them and
    a collection between them (seconds)."""
    R = Record
    return [R("serve.queue", 0.0, 1.0, None, 0),
            R("serve.sync", 1.4, 1.5, "serve.prefill", None),
            R("serve.prefill", 1.0, 1.5, "serve.step", 0),
            R("serve.decode", 1.5, 1.7, "serve.step", None),
            R("serve.sync", 1.7, 1.95, "serve.step", None),
            R("serve.step", 1.0, 2.0, None, None),
            R("host.gc", 2.5, 2.6, None, 0),
            R("serve.queue", 0.5, 3.0, None, 1),
            R("serve.sync", 3.1, 3.2, "serve.prefill", None),
            R("serve.prefill", 3.0, 3.2, "serve.step", 1),
            R("serve.decode", 3.2, 3.3, "serve.step", None),
            R("host.gc", 3.3, 3.4, "serve.step", 0),
            R("serve.sync", 3.4, 3.5, "serve.step", None),
            R("serve.step", 3.0, 3.6, None, None),
            R("serve.step", 11.0, 12.0, None, None)]       # after the window


def test_summary_of_the_windows_records():
    line = engine_spans.summary(records(), 0.0, 10.0, {"steps": 2})
    spans = line["spans"]
    assert spans["serve.step"]["count"] == 2
    assert spans["serve.step"]["total_s"] == pytest.approx(1.6)
    assert spans["serve.step"]["self_s"] == pytest.approx(0.05 + 0.1)
    assert spans["serve.prefill"]["self_s"] == pytest.approx(0.4 + 0.1)
    assert spans["host.gc"] == {"count": 2, "total_s": pytest.approx(0.2),
                                "self_s": pytest.approx(0.2)}
    assert line["counters"] == {"steps": 2} and line["long_steps"] == 2
    first = line["longest"][0]
    assert (first["t_s"], first["s"]) == (1.0, 1.0)
    assert first["within"] == {"serve.prefill": pytest.approx(0.5),
                               "serve.sync": pytest.approx(0.35),
                               "serve.decode": pytest.approx(0.2)}
    assert line["longest"][1]["within"]["host.gc"] == pytest.approx(0.1)
    assert line["metrics"] == {
        "queue_wait_p95_ms.serve": pytest.approx(1e3 * (1.0 + 0.95 * 1.5)),
        "prefill_ms.serve": pytest.approx(350.0),
        "engine_host_ms.serve": pytest.approx(1e3 * (0.65 + 0.4) / 2)}


def test_spans_tool_rehearsal(tmp_path):
    rc, lines, last, err = rehearse.run(
        rehearse.cell_args("granite-8b.serve_chat", trace=1), tmp_path,
        tool="tools/spans.py")
    assert rc == 0, err[-4000:]
    result = json.loads(lines[-2])
    assert result["correct"] is True and "engine_step_ms.serve" in \
        result["metrics"]
    assert last["phase"] == "spans" and last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"queue_wait_p95_ms.serve",
                                    "prefill_ms.serve", "engine_host_ms.serve"}
    c = last["counters"]
    assert c["prefills"] >= result["attempted"] and c["steps"] > 0
    assert 0 < last["spans"]["serve.queue"]["count"] <= result["attempted"]
