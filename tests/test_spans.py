"""The span recorder: nesting and request ids, its bound, the garbage
collector's spans, the profiler's host plane, and an engine handed none."""

import gc
import glob

import jax
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.serve.engine import ServeEngine
from repro.spans import Spans


def test_nesting_sets_parent_and_request_id():
    with Spans() as spans:
        with spans("a"):
            with spans("b", 7):
                pass
            with spans("c"):
                with spans("d", 7):
                    pass
        with pytest.raises(ValueError):
            with spans("e"):
                raise ValueError
        with spans("f"):
            pass
    recs = list(spans.records)
    assert [r.name for r in recs] == ["b", "d", "c", "a", "e", "f"]
    by = {r.name: r for r in recs}
    assert [by[n].parent for n in "abcdef"] == [None, "a", "a", "c", None,
                                                None]
    assert by["a"].t0 <= by["b"].t0 <= by["b"].t1 <= by["c"].t0 \
        <= by["d"].t0 <= by["d"].t1 <= by["c"].t1 <= by["a"].t1
    assert [r.name for r in recs if r.attr == 7] == ["b", "d"]
    spans.add("wait", 1.0, 2.0, 7)
    assert spans.records[-1] == ("wait", 1.0, 2.0, None, 7)


def test_the_bound_keeps_the_newest():
    with Spans(maxlen=3) as spans:
        for i in range(5):
            with spans(f"s{i}"):
                pass
    assert [r.name for r in spans.records] == ["s2", "s3", "s4"]


def test_garbage_collections_are_spans_until_closed():
    spans = Spans()
    with spans("outer"):
        gc.collect()
    gcs = [r for r in spans.records if r.name == "host.gc"]
    assert gcs and gcs[-1].parent == "outer" and gcs[-1].attr == 2
    outer = spans.records[-1]
    assert outer.t0 <= gcs[-1].t0 <= gcs[-1].t1 <= outer.t1
    spans.close()
    n = len(spans.records)
    gc.collect()
    assert len(spans.records) == n and spans._on_gc not in gc.callbacks


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    with Spans() as spans:
        jax.profiler.start_trace(str(tmp_path))
        with spans("outer"):
            with spans("inner"):
                gc.collect()
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"outer", "inner", "host.gc"} <= names


def test_an_engine_without_a_recorder_never_calls_the_profiler(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("jax.profiler called")

    for name in dir(jax.profiler):
        if not name.startswith("_") and callable(getattr(jax.profiler, name)):
            monkeypatch.setattr(jax.profiler, name, refuse)
    callbacks = list(gc.callbacks)
    cfg = configs.get_smoke("tiny").replace(dtype="float32")
    mesh = make_mesh(jax.devices()[:1], (1, 1))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, mesh, shd.make_rules(multi_pod=False), params,
                         max_batch=2, max_len=32)
    rng = np.random.default_rng(0)
    with mesh:
        for _ in range(3):
            engine.submit(rng.integers(0, cfg.vocab_size, 5).tolist(),
                          max_new_tokens=3)
        done = engine.run()
    assert engine.spans is None and gc.callbacks == callbacks
    assert all(r.done and len(r.generated) == 3 for r in done)
