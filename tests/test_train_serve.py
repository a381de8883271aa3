"""Data-plane integration: checkpoint/restart, preemption, determinism of
the data pipeline, and the continuous-batching serving engine."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.data.pipeline import data_iterator
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.parallel.steps import init_train_state
from repro.serve.engine import ServeEngine
from repro.train import checkpoint as ckpt
from repro.train.loop import train_loop


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices()[:1], (1, 1))


CFG = configs.get_smoke("tiny").replace(dtype="float32")
RULES = shd.make_rules(multi_pod=False)


def test_checkpoint_roundtrip(tmp_path):
    state = init_train_state(CFG, jax.random.PRNGKey(0))
    ckpt.save(str(tmp_path), state, 7)
    restored, step = ckpt.restore_latest(str(tmp_path), state)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_keeps_latest(tmp_path):
    state = init_train_state(CFG, jax.random.PRNGKey(0))
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), state, s, keep=2)
    assert ckpt.list_steps(str(tmp_path)) == [4, 5]


def test_train_restart_is_deterministic(mesh, tmp_path):
    """Train 6 steps straight vs 3 steps + restart + 3 steps: identical."""
    kw = dict(steps=6, global_batch=2, seq_len=16, ckpt_every=3, seed=1)
    with mesh:
        full = train_loop(CFG, mesh, RULES, ckpt_dir=str(tmp_path / "a"), **kw)
        part = train_loop(CFG, mesh, RULES, ckpt_dir=str(tmp_path / "b"),
                          **{**kw, "steps": 3})
        resumed = train_loop(CFG, mesh, RULES, ckpt_dir=str(tmp_path / "b"),
                             **kw)
    assert resumed.status == "done" and resumed.step == 6
    assert abs(full.metrics["loss"] - resumed.metrics["loss"]) < 1e-5


def test_train_preemption_checkpoints(mesh, tmp_path):
    calls = {"n": 0}

    def preempt_after_4():
        calls["n"] += 1
        return calls["n"] > 4

    with mesh:
        res = train_loop(CFG, mesh, RULES, steps=100, global_batch=2,
                         seq_len=16, ckpt_dir=str(tmp_path),
                         preempt_check=preempt_after_4)
    assert res.status == "preempted"
    assert ckpt.latest_step(str(tmp_path)) == res.step


def test_train_loop_compiles_its_step_once(mesh):
    """A fresh state is placed on the step's shardings, so the first call and
    every later one (fed the step's own outputs) run one compiled program."""
    compiles = []

    def listen(event, secs, **kw):
        if (event == "/jax/core/compile/backend_compile_duration"
                and kw.get("fun_name") == "jit(train_step)"):
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with mesh:
            res = train_loop(CFG, mesh, RULES, steps=3, global_batch=2,
                             seq_len=16)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert res.step == 3
    assert len(compiles) == 1


def test_data_iterator_deterministic_and_resumable():
    a = data_iterator(CFG, 2, 16, seed=3)
    b = data_iterator(CFG, 2, 16, seed=3)
    x1, x2 = next(a), next(b)
    np.testing.assert_array_equal(np.asarray(x1["tokens"]),
                                  np.asarray(x2["tokens"]))
    # resume from step 2 matches streaming past it
    next(a)
    third = next(a)
    c = data_iterator(CFG, 2, 16, seed=3, start_step=2)
    np.testing.assert_array_equal(np.asarray(next(c)["tokens"]),
                                  np.asarray(third["tokens"]))
    for it in (a, b, c):
        it.close()


def test_serve_engine_completes_all_and_greedy_matches_reference(mesh):
    cfg = configs.get_smoke("granite-8b").replace(dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rules = shd.make_rules(multi_pod=False)
    engine = ServeEngine(cfg, mesh, rules, params, max_batch=2, max_len=48)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, int(rng.integers(3, 10))).tolist()
               for _ in range(5)]
    with mesh:
        for pr in prompts:
            engine.submit(pr, max_new_tokens=4)
        done = engine.run(max_steps=200)
    assert len(done) == 5
    assert all(len(r.generated) == 4 for r in done)
    # row 0's first generated token must equal single-request greedy decode
    logits, _ = M.prefill(params, cfg,
                          {"tokens": jnp.asarray([prompts[0]])}, 48)
    expect = int(jnp.argmax(logits, -1)[0])
    assert done[0].generated[0] == expect or any(
        r.prompt == prompts[0] and r.generated[0] == expect for r in done)


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_compile_cache_placement(monkeypatch, env):
    """An outside JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise the cache goes to the fixed, git-ignored <repo>/.jax_cache."""
    import pathlib

    from repro.launch import cache

    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = cache.configure_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env is not None:
        assert (got, now) == (env, before)
        return
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert got == now == str(repo / ".jax_cache")
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
