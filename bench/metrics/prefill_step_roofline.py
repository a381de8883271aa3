"""prefill_step_roofline: the least time of one prefill over the device
time of one call of the ``prefill_step`` programs in the trace, in percent:
the mean least time of the prefills whose steps ended in the traced window,
over those programs' device seconds per call there. The least time is the
larger of the prefill's FLOPs over the bf16 peak and its needed bytes over
the HBM peak, from the reference module's ``prefill_cost``. Nothing is read
where the reference module has no ``prefill_cost`` or no prefill ran."""

PROGRAM = "prefill_step"


def read(cell):
    tf = cell.trace_facts
    cost = getattr(cell.model, "prefill_cost", None)
    if tf is None or cell.peaks is None or cost is None:
        return None
    calls, device_s = tf.module(PROGRAM)
    lo, hi = cell.facts.get("trace_start"), cell.facts.get("trace_stop")
    lengths = [n for t, prefilled, _ in cell.facts["steps"]
               if lo <= t <= hi for n in prefilled]
    if not calls or not lengths:
        return None
    least = 0.0
    for n in lengths:
        flops, nbytes = cost(cell.config, n)
        least += max(flops / cell.peaks["bf16_flops"],
                     nbytes / cell.peaks["hbm_bytes_per_s"])
    return 100.0 * (least / len(lengths)) / (device_s / calls)
