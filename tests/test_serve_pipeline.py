"""The serving engine keeps one decode in flight: its tokens against a plain
synchronous loop, and the counters of what the pipeline overlapped and
dropped."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.serve.engine import ServeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RULES = shd.make_rules(multi_pod=False)
B, MAX_LEN = 3, 32


def _bench_smoke(name):
    """A benchmark configuration at its tiny sizes, as ``tests/test_mla_moe.py``
    and ``tests/test_kda.py`` build it: moonshot-v1-16b-a3b (latent
    attention, a dense lead layer, shared experts) and kimi-linear-48b-a3b
    (KDA layers' recurrent state beside latent rows, half the experts
    held)."""
    raw = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      name + ".json")))
    c = {**raw, **raw["tiny"]}
    fields = {f: c[k] for f, k in raw["program"]["fields"].items()}
    return configs.get_smoke(raw["program"]["arch"]).replace(**fields)


CFGS = {"granite-8b": lambda: configs.get_smoke("granite-8b").replace(
            dtype="float32"),
        "moonshot-v1-16b-a3b": lambda: _bench_smoke("moonlight-16b-a3b"),
        "kimi-linear-48b-a3b": lambda: _bench_smoke("kimi-linear-48b-a3b")}


@pytest.fixture(scope="module", params=sorted(CFGS))
def model(request):
    cfg = CFGS[request.param]()
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices()[:1], (1, 1))


def synchronous(cfg, params, reqs, max_batch, max_len):
    """Greedy serving with no decode in flight: FIFO admission into free
    slots, a batch-1 ``M.prefill`` spliced into the batched cache, one
    ``M.decode_step`` over every slot, and the argmax read on the host
    before the next step. ``reqs``: (prompt, max_new_tokens, eos_id)."""
    prefill = jax.jit(lambda p, t: M.prefill(p, cfg, {"tokens": t}, max_len))
    decode = jax.jit(lambda p, c, t, q: M.decode_step(p, cfg, c, t, q))
    cache = M.init_cache(cfg, max_batch, max_len)
    queue = list(range(len(reqs)))
    out = [[] for _ in reqs]
    rows = [None] * max_batch              # (request, next position)
    while queue or any(rows):
        for b in range(max_batch):
            if rows[b] is not None or not queue:
                continue
            i = queue.pop(0)
            prompt, budget, eos = reqs[i]
            logits, row = prefill(params, jnp.asarray([prompt], jnp.int32))
            cache = jax.tree_util.tree_map_with_path(
                lambda path, full, r: full.at[:, b].set(r[:, 0])
                if M.stacked(path) else full.at[b].set(r[0]), cache, row)
            out[i].append(int(np.argmax(np.asarray(logits[0]))))
            if budget > 1 and out[i][-1] != eos:
                rows[b] = (i, len(prompt))
        if not any(rows):
            continue
        tokens = np.zeros((max_batch, 1), np.int32)
        pos = np.zeros((max_batch,), np.int32)
        for b, r in enumerate(rows):
            if r is not None:
                tokens[b, 0], pos[b] = out[r[0]][-1], r[1]
        logits, cache = decode(params, cache, jnp.asarray(tokens),
                               jnp.asarray(pos))
        nxt = np.argmax(np.asarray(logits), axis=-1)
        for b, r in enumerate(rows):
            if r is None:
                continue
            i, p = r
            out[i].append(int(nxt[b]))
            _, budget, eos = reqs[i]
            rows[b] = (i, p + 1)
            if len(out[i]) >= budget or out[i][-1] == eos or \
                    p + 1 >= max_len - 1:
                rows[b] = None
    return out


def test_pipelined_engine_matches_synchronous_loop(model, mesh):
    """More requests than slots, a one-token answer, an EOS planted at a
    token the loop produces, and a row stopped by the cache's length: the
    engine's tokens are the synchronous loop's, token for token."""
    cfg, params = model
    rng = np.random.default_rng(5)
    lengths = (5, 9, 5, 24, 9, 5, 9)
    budgets = (6, 1, 8, 20, 4, 7, 5)
    reqs = [(rng.integers(0, cfg.vocab_size, n).tolist(), k, None)
            for n, k in zip(lengths, budgets)]
    # request 2 stops at the token it would produce third
    eos = synchronous(cfg, params, reqs[2:3], B, MAX_LEN)[0][2]
    reqs[2] = (reqs[2][0], reqs[2][1], eos)
    want = synchronous(cfg, params, reqs, B, MAX_LEN)
    assert want[2][-1] == eos and len(want[2]) <= 3
    assert len(want[1]) == 1
    assert len(want[3]) == MAX_LEN - 1 - lengths[3] + 1 < budgets[3]

    with mesh:
        engine = ServeEngine(cfg, mesh, RULES, params, max_batch=B,
                             max_len=MAX_LEN)
        for prompt, k, e in reqs:
            engine.submit(prompt, max_new_tokens=k, eos_id=e)
        done = engine.run(max_steps=200)
    assert [r.generated for r in done] == want
    assert all(r.done for r in done)
    assert not engine.step()                   # nothing left in flight
    c = engine.counters
    assert c.decoded_tokens == sum(len(g) - 1 for g in want)
    assert 0 < c.overlapped_steps <= c.steps


def test_pipeline_counters_match_hand_count(mesh):
    """Two slots, answers of 3, 1 and 2 tokens.

    step 1: admit r0, r1; decode D1 (r0, r1); read the first tokens: r1 is
            done.
    step 2: admit r2 into r1's slot; D2 (r0, r2) overlaps D1; read D1: r0's
            second token, r1's dropped; r2's first token.
    step 3: D3 (r0, r2) overlaps D2; read D2: r0 and r2 are done, so no
            row is active and D3's two tokens are dropped unread.

    So 2 decodes were dispatched with one unread (D2, D3), 3 tokens were
    dropped (one a request), and 2 decodes handed tokens (D1, D2)."""
    cfg = configs.get_smoke("tiny").replace(dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(6)
    with mesh:
        engine = ServeEngine(cfg, mesh, RULES, params, max_batch=2,
                             max_len=MAX_LEN)
        for k in (3, 1, 2):
            engine.submit(rng.integers(0, cfg.vocab_size, 6).tolist(),
                          max_new_tokens=k)
        assert [engine.step() for _ in range(3)] == [True, True, False]
        done = [engine.requests[r] for r in sorted(engine.requests)]
        assert [len(r.generated) for r in done] == [3, 1, 2]
        c = engine.counters
        assert (c.overlapped_steps, c.discarded_tokens, c.steps,
                c.decoded_tokens) == (2, 3, 2, 3)
        # nothing is left in flight: a further step reads and does nothing
        before = dict(vars(c))
        assert not engine.step()
        assert vars(engine.counters) == before


def test_serve_step_returns_argmax_and_next_positions(model, mesh):
    """The jitted decode returns the host argmax of ``M.decode_step``'s
    logits and each row's next position; a row at position 0 stays idle
    and decodes token 0."""
    from repro.parallel.steps import make_serve_step

    cfg, params = model
    step = make_serve_step(cfg, mesh, RULES, global_batch=B, max_len=MAX_LEN)
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    pos = jnp.asarray([3, 0, 11], jnp.int32)
    logits, _ = jax.jit(lambda p, c, t, q: M.decode_step(p, cfg, c, t, q))(
        params, M.init_cache(cfg, B, MAX_LEN), tokens, pos)
    with mesh:
        nxt, new_pos, _ = step(params, M.init_cache(cfg, B, MAX_LEN), tokens,
                               pos)
    want = np.argmax(np.asarray(logits), axis=-1)
    want[1] = 0
    np.testing.assert_array_equal(np.asarray(nxt)[:, 0], want)
    assert np.asarray(nxt).dtype == np.int32
    np.testing.assert_array_equal(np.asarray(new_pos), [4, 0, 12])


def test_finished_row_idles_on_device(mesh):
    """A row whose request finished, with nothing queued for it, is set to
    position 0 and token 0 before the next decode and stays there, so its
    attention reads one position and not its old request's whole length."""
    cfg = configs.get_smoke("tiny").replace(dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(8)
    with mesh:
        engine = ServeEngine(cfg, mesh, RULES, params, max_batch=2,
                             max_len=MAX_LEN)
        for k in (8, 2):
            engine.submit(rng.integers(0, cfg.vocab_size, 6).tolist(),
                          max_new_tokens=k)
        for _ in range(2):      # r1's second token is read at step 2
            engine.step()
        assert engine.requests[1].done
        for _ in range(2):      # step 3 idles its row before decoding
            engine.step()
            assert list(np.asarray(engine._pos)) == [6 + engine.steps_run + 1,
                                                     0]
            assert int(np.asarray(engine._tokens)[1, 0]) == 0
        engine.run()
    assert [len(engine.requests[r].generated) for r in (0, 1)] == [8, 2]
    # idling a row and admitting one call the same compiled patch, so the
    # first row to idle after a warm-up compiles nothing
    assert engine._patch._cache_size() == 1


def test_each_splice_waits_for_the_cache_it_replaces(mesh):
    """Three admissions in one step: each waits for the cache its splice
    replaces (each splice allocates a whole cache), then the step makes
    its one read."""
    from repro.spans import Spans

    cfg = configs.get_smoke("tiny").replace(dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    with Spans() as spans, mesh:
        engine = ServeEngine(cfg, mesh, RULES, params, max_batch=3,
                             max_len=MAX_LEN, spans=spans)
        for _ in range(3):
            engine.submit(rng.integers(0, cfg.vocab_size, 6).tolist(),
                          max_new_tokens=4)
        engine.step()
    names = [r.name for r in spans.records]
    assert names.count("serve.prefill") == 3
    assert names.count("serve.sync") == 3 + 1
