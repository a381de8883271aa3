"""step_mfu.serve: model FLOPs of the engine steps that ended in the traced
window (each prompt prefilled and each active row's decoded token) over the
seconds in which the device ran an operation in that window, times the
chip's bf16 peak, in percent: the whole step's share of the peak, copies and
host syncs included, and not the rate the traffic offers."""


def read(cell):
    tf = cell.trace_facts
    if tf is None or cell.peaks is None or tf.busy_s <= 0:
        return None
    c, model = cell.config, cell.model
    lo, hi = cell.facts.get("trace_start"), cell.facts.get("trace_stop")
    flops = 0.0
    for t, prefilled, live in cell.facts["steps"]:
        if lo <= t <= hi:
            flops += sum(model.prefill_flops(c, n) for n in prefilled)
            flops += model.decode_cost(c, live)[0] if live else 0.0
    if not flops:
        return None
    return 100.0 * flops / (tf.busy_s * cell.peaks["bf16_flops"])
