"""setup_s: process start to the opening of the measured window (host
clock): imports, device start, store and weights, compiles or cache loads,
and the warm-up steps or requests."""


def read(cell):
    return cell.facts["window_start"] - cell.t0
