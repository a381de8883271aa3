"""device_idle_share.serve: 1 - (union of device-op intervals / traced
window), in percent, from the serving cell's trace."""


def read(cell):
    tf = cell.trace_facts
    return None if tf is None else 100.0 * tf.idle_share
