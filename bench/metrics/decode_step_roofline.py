"""decode_step_roofline: the least time of one decode call over the device
time of that call (the ``serve_step`` program in the trace), in percent,
averaged over the calls in the traced window. The least time is the larger
of the call's FLOPs over the bf16 peak and its needed bytes over the HBM
peak; needed bytes are the weights and the keys and values of each active
row's live length, not the cache's whole length."""

PROGRAM = "serve_step"


def read(cell):
    tf = cell.trace_facts
    if tf is None or cell.peaks is None:
        return None
    calls, device_s = tf.module(PROGRAM)
    lo, hi = cell.facts.get("trace_start"), cell.facts.get("trace_stop")
    live = [lv for t, _, lv in cell.facts["steps"] if lo <= t <= hi and lv]
    if not calls or not live:
        return None
    least = 0.0
    for lv in live:
        flops, nbytes = cell.model.decode_cost(cell.config, lv)
        least += max(flops / cell.peaks["bf16_flops"],
                     nbytes / cell.peaks["hbm_bytes_per_s"])
    return 100.0 * (least / len(live)) / (device_s / calls)
