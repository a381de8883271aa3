"""CPU rehearsal of the serving driver at tiny sizes, a token altered where
the engine produces it turning ``correct`` false, no result without a chip,
the checked sample, and the readers that take the engine's steps."""

import json
import os
from types import SimpleNamespace as NS

import pytest

import rehearse

from bench.lib import harness

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_serve_chat_rehearsal(tmp_path):
    rc, lines, last, err = rehearse.run(
        rehearse.cell_args("granite-8b.serve_chat"), tmp_path)
    assert rc == 0, err[-4000:]
    assert list(last)[:5] == KEYS and list(last)[-1] == "checks"
    assert last["correct"] is True and last["attempted"] > 20
    assert set(last["metrics"]) == {"ttft_p95_s", "tpot_p95_s", "setup_s"}
    assert last["checks"]["never_done"]["value"] == 0
    assert any('"phase": "generator"' in ln for ln in lines)
    for line in lines:
        dev = json.loads(line)["device"]
        assert (dev["platform"], dev["count"]) == ("cpu", 1)
    check = next(json.loads(ln) for ln in lines if '"phase": "check"' in ln)
    assert check["sampled"] == 4


def test_altered_token_is_caught(tmp_path):
    rc, _, last, err = rehearse.run(
        rehearse.cell_args("granite-8b.serve_chat"), tmp_path,
        fault="token_altered")
    assert rc == 0, err[-4000:]
    gap = last["checks"]["logit_gap"]
    assert last["correct"] is False and gap["value"] > gap["limit"]


def test_without_a_chip_there_is_no_result(tmp_path):
    args = rehearse.cell_args("granite-8b.serve_chat")[:-1]      # no --tiny
    rc, lines, last, err = rehearse.run(args, tmp_path)
    assert rc != 0 and not lines and "TPU" in err


def driver():
    return harness.load_module(os.path.join(harness.BENCH, "drivers",
                                            "serve.py"))


def test_sample_takes_the_longest_and_one_per_stretch():
    done = {rid: NS(prompt=[0] * (8 if rid != 13 else 32), generated=[1] * 4)
            for rid in range(40)}
    picked = [r for r in driver().sample(done, 2**31 + 7, 4)]
    rids = [next(k for k, v in done.items() if v is r) for r in picked]
    assert rids[0] == 13 and len(rids) == 5
    rest = sorted(set(range(40)) - {13})
    stretches = [rest[i * 39 // 4:(i + 1) * 39 // 4 + 1] for i in range(4)]
    assert all(any(r in s for r in rids[1:]) for s in stretches)
    assert driver().sample(done, 2**31 + 7, 4) == picked
    assert driver().sample({}, 1, 4) == []


@pytest.fixture
def cell():
    cfg = harness.merged(harness.load_json(os.path.join(
        harness.ROOT, "bench", "configs", "granite-8b.json")), True)
    model = harness.load_module(os.path.join(harness.BENCH, "configs",
                                             "granite-8b.py"))
    steps = [(0.5, [], [9]), (1.5, [8], [10, 9]), (2.5, [16], []),
             (9.0, [32], [40])]                    # the last ends after it
    tf = NS(busy_s=0.5, module=lambda name: (2.0, 0.02) if name == "serve_step"
            else (0.0, 0.0))
    return NS(config=cfg, model=model, trace_facts=tf,
              peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
              facts={"trace_start": 1.0, "trace_stop": 3.0, "steps": steps})


def read(name, cell):
    return harness.load_module(os.path.join(harness.BENCH, "metrics",
                                            name + ".py")).read(cell)


def test_step_mfu_counts_the_traced_steps_over_busy_time(cell):
    m, c = cell.model, cell.config
    flops = (m.prefill_flops(c, 8) + m.decode_cost(c, [10, 9])[0]
             + m.prefill_flops(c, 16))
    assert read("step_mfu.serve", cell) == pytest.approx(
        100 * flops / (0.5 * 1e12))
    cell.trace_facts = None
    assert read("step_mfu.serve", cell) is None


def test_decode_roofline_over_the_calls_device_time(cell):
    flops, nbytes = cell.model.decode_cost(cell.config, [10, 9])
    least = max(flops / 1e12, nbytes / 1e11)
    assert read("decode_step_roofline", cell) == pytest.approx(
        100 * least / 0.01)


def test_every_seed_offers_one_schedule_with_its_own_tokens():
    drv, cfg = driver(), harness.load_json(os.path.join(
        harness.BENCH, "configs", "granite-8b.json"))
    tr = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                        "serve_chat.json"))
    model = harness.load_module(os.path.join(harness.BENCH, "configs",
                                             "granite-8b.py"))
    a, b = (drv.requests(cfg, tr, model, s, 51) for s in (2**31 + 5, 6))
    shape = lambda rs: [(r["due"], len(r["prompt"]), r["max_new"])  # noqa: E731
                        for r in rs]
    assert shape(a) == shape(b) and len(a) > 200
    assert a[0]["prompt"] != b[0]["prompt"]
    assert a == drv.requests(cfg, tr, model, 2**31 + 5, 51)
