"""Serving driver: an open loop of requests into ``ServeEngine``.

The scheduler cannot place a serving replica yet, so the benchmark drives
``ServeEngine.submit``/``step`` itself, on one chip, with weights made on
the device from the seed. Arrivals are a Poisson process at the traffic
file's fixed rate: every seed offers the same gaps, prompt lengths and
answer lengths (gaps at the exponential's quantiles, prompt lengths in the
stated proportions, answer lengths at the clipped lognormal's quantiles)
in one order, shuffled once by the traffic file's ``order_seed``, with the
seed's own token ids: an order that fills every slot at once moves the
tail by several times, so the seed may not choose it.

Each request is timed from when it was due. Requests are submitted between
engine steps, so the generator runs late by up to one step; how late is
printed. After ``--seconds`` no request is sent and the engine runs until
every request that arrived has finished, ``drain_seconds`` at most.

What each engine step did is read from the engine's public state around
the call: the requests that left the queue were prefilled, and each row
whose answer grew by a token in the decode was one of its active rows.

``correct``: every request that arrived finished, and for a sample drawn
from the seed (the longest request, and one from each of ``check.blocks``
stretches of the window in admission order) the widest gap by which a
served token's logit lies below the best logit of the configuration's
float32 reference, run over the prompt and the served tokens once the
engine is freed.
"""

from __future__ import annotations

import gc
import statistics
import time
from collections import deque

import numpy as np

from bench.lib import compare, lowp
from bench.lib.harness import peak_bytes
from bench.lib.program import program_config

# set by bench/tools/readings.py: the check also reads the fp8 control
CONTROL = False


def requests(c: dict, tr: dict, model, seed: int, seconds: float) -> list[dict]:
    """The arrivals of one run: due time (s after the window opens),
    prompt token ids and answer budget."""
    rng = np.random.default_rng([tr["order_seed"], 2])
    rate = tr["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    due = np.cumsum(rng.permutation(-np.log1p(-q) / rate))
    counts = np.floor(np.asarray(tr["prompt_weights"]) * n).astype(int)
    counts[np.argmax(tr["prompt_weights"])] += n - counts.sum()
    plens = rng.permutation(np.repeat(tr["prompt_lengths"], counts))
    out = tr["output"]
    z = np.asarray([statistics.NormalDist().inv_cdf(x) for x in q])
    answers = np.clip(np.round(out["median"] * np.exp(out["sigma"] * z)),
                      out["min"], out["max"]).astype(int)
    answers = rng.permutation(answers)
    rng = np.random.default_rng([seed, 2])
    return [{"due": float(t), "prompt": model.tokens(c, rng, int(p)),
             "max_new": int(a)}
            for t, p, a in zip(due, plens, answers) if t < seconds]


def run(cell) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.parallel import sharding as shd
    from repro.serve.engine import ServeEngine

    tr, c, model = cell.traffic, cell.config, cell.model
    _, cfg = program_config(cell)
    seed = cell.seed % 2**31
    W = cell.seconds
    mesh = make_mesh(cell.devices[:1], (1, 1))
    params = model.make_weights(c, seed, jnp.dtype(cfg.dtype))
    engine = ServeEngine(cfg, mesh, shd.make_rules(multi_pod=False), params,
                         max_batch=tr["max_batch"], max_len=tr["max_len"])

    arrivals = requests(c, tr, model, seed, W)
    warm_rng = np.random.default_rng([seed, 4])
    with mesh:
        for plen in tr["prompt_lengths"]:           # every prefill shape
            engine.submit(model.tokens(c, warm_rng, plen), max_new_tokens=2)
        engine.run()
        jax.block_until_ready(engine.cache)
        # what set-up made lives on: later collections need not walk it
        gc.collect()
        gc.freeze()

        pending = deque(arrivals)
        recs: dict[int, dict] = {}
        inflight: list[int] = []
        steps = []                      # (end, prefilled lengths, live rows)
        t_start = time.perf_counter()
        cell.facts["window_start"] = t_start
        cell.trace_start()
        deadline = W + tr["drain_seconds"]
        while True:
            now = time.perf_counter() - t_start
            while pending and pending[0]["due"] <= now and now < W:
                a = pending.popleft()
                rid = engine.submit(a["prompt"], max_new_tokens=a["max_new"])
                recs[rid] = {"due": a["due"], "sent": now, "first": None,
                             "last": None}
                inflight.append(rid)
            if cell.trace_due or (now >= W and cell.trace):
                cell.trace_stop()
            if now >= deadline or (now >= W and not inflight):
                break
            if not inflight:
                nxt = pending[0]["due"] if pending else W
                time.sleep(max(0.0, min(nxt, W) - now))
                continue
            queued = {req.rid for req in engine.queue}
            had = {rid: len(engine.requests[rid].generated) for rid in inflight}
            decodes = engine.steps_run
            with cell.spans("bench.engine_step"):
                engine.step()
            end = time.perf_counter()
            t = end - t_start
            decoded = engine.steps_run > decodes
            prefilled, live = [], []
            for rid in list(inflight):
                r, req = recs[rid], engine.requests[rid]
                grew = len(req.generated) - had[rid]
                if rid in queued and grew:
                    prefilled.append(len(req.prompt))
                    grew -= 1
                if grew and decoded:     # its cache holds prompt + answer - 1
                    live.append(len(req.prompt) + len(req.generated) - 1)
                if r["first"] is None and req.generated:
                    r["first"] = t
                if req.done:
                    r["last"] = t
                    inflight.remove(rid)
            steps.append((end, prefilled, live))
        t_end = t_start + W
        cell.trace_stop()
    steps_run = engine.steps_run

    # ------------------------------------------------------------ measured
    never = [rid for rid, r in recs.items() if r["last"] is None]
    ttft = [(r["first"] if r["first"] is not None else deadline) - r["due"]
            for r in recs.values()]
    tpot = [(r["last"] - r["first"]) / (len(engine.requests[rid].generated) - 1)
            for rid, r in recs.items()
            if r["last"] is not None and len(engine.requests[rid].generated) > 1]
    late = [r["sent"] - r["due"] for r in recs.values()]
    cell.facts.update({
        "window_s": W, "ttft_s": ttft, "tpot_s": tpot,
        "requests": len(recs), "steps_run": steps_run,
        "engine_step_s": cell.spans.durations("bench.engine_step",
                                              t_start, t_end),
        "steps": steps,
        "memory_peak_bytes": peak_bytes(cell.devices)})
    cell.attempted, cell.failed = len(recs), len(never)
    compiles, compile_s, _ = cell.compile_log.between(t_start, t_end)
    cell.emit({"phase": "generator", "requests": len(recs),
               "late_mean_s": float(np.mean(late)) if late else 0.0,
               "late_max_s": float(np.max(late)) if late else 0.0,
               "decode_steps": steps_run,
               "window_compiles": compiles, "window_compile_s": compile_s})
    cell.check("never_done", len(never))

    # ------------------------------------------------------------- checks
    done = {rid: engine.requests[rid] for rid in recs if rid not in never}
    del engine, params
    gc.unfreeze()
    gc.collect()
    t0 = time.perf_counter()
    picked = sample(done, seed, cell.traffic["check"]["blocks"])
    gap, control = reference_gaps(cell, picked, seed, CONTROL)
    cell.facts["check_s"] = time.perf_counter() - t0
    cell.emit({"phase": "check", "sampled": len(picked),
               "served_tokens": sum(len(r.generated) for r in picked),
               "logit_gap": gap, "check_s": cell.facts["check_s"]})
    if CONTROL:
        cell.emit({"variant": "control", "seed": cell.seed,
                   "logit_gap": control, "program_logit_gap": gap})
    cell.check("logit_gap", gap)


def sample(done: dict, seed: int, blocks: int) -> list:
    """The longest finished request, then one drawn from the seed out of
    each of ``blocks`` equal stretches of the others in admission order, so
    that the sample spans the window's slots and admission times."""
    if not done:
        return []
    longest = max(done, key=lambda rid: (len(done[rid].prompt)
                                         + len(done[rid].generated), -rid))
    rest = sorted(rid for rid in done if rid != longest)
    rng = np.random.default_rng([seed, 3])
    picked = [longest] + [int(rng.choice(part)) for part in
                          np.array_split(rest, min(blocks, len(rest)))
                          if len(part)]
    return [done[rid] for rid in picked]


def reference_gaps(cell, picked: list, seed: int, control: bool = False):
    """(widest gap of a served token under the float32 reference, and with
    ``control`` the widest gap of the token the fp8 reference puts first at
    the same positions, else None)."""
    import functools

    import jax
    import jax.numpy as jnp

    if not picked:
        return None, None
    c, model, bucket = cell.config, cell.model, cell.traffic["check"]["bucket"]
    params = model.make_weights(c, seed, jnp.dtype(c["torch_dtype"]))
    ref = jax.jit(functools.partial(model.logits, c=c, num=lowp.F32))
    low = jax.jit(functools.partial(model.logits, c=c, num=lowp.FP8))
    worst, worst_low = 0.0, None
    for req in picked:
        seq = req.prompt + req.generated[:-1]
        size = -(-len(seq) // bucket) * bucket
        toks = jnp.asarray(seq + [0] * (size - len(seq)), jnp.int32)
        lo, n = len(req.prompt) - 1, len(req.generated)
        rows = np.asarray(ref(params, toks)[lo:lo + n])
        worst = max(worst, compare.served_gap(rows, req.generated))
        if control:
            q = np.asarray(low(params, toks)[lo:lo + n])
            worst_low = max(worst_low or 0.0, compare.control_gap(rows, q))
    return worst, worst_low
