"""The control: the reference in fp8 in the program's place fails the
cell's logit gap. At the tiny sizes, on the CPU, against the tiny limit
(the program runs float32 there)."""

import json

import rehearse

from bench.lib import harness


def readings(tmp_path, workload, seeds):
    rc, lines, _, err = rehearse.run(
        ["--workload", workload, "--seeds", seeds, "--seconds", "3", "--tiny"],
        tmp_path, tool="tools/readings.py")
    assert rc == 0, err[-4000:]
    return [json.loads(ln) for ln in lines if '"variant"' in ln]


def tiny_limits(traffic):
    path = f"{harness.BENCH}/traffic/{traffic}.json"
    return harness.merged(harness.load_json(path), True)["limits"]


def test_serve_control_fails_the_gap(tmp_path):
    limit = tiny_limits("serve_chat")["logit_gap"]
    rows = readings(tmp_path, "granite-8b.serve_chat", seeds="5")
    assert rows and all(r["logit_gap"] > limit >= r["program_logit_gap"]
                        for r in rows)
