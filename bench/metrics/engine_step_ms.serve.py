"""engine_step_ms.serve: mean milliseconds of ``ServeEngine.step()`` in the
window (admission and prefill, one decode, the host's argmax), from the
benchmark's timer around each call."""


def read(cell):
    d = cell.facts["engine_step_s"]
    return 1e3 * sum(d) / len(d) if d else None
