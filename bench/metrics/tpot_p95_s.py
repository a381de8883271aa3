"""tpot_p95_s: 95th percentile, over the requests that arrived in the
window and finished, of (last token time - first token time) / (tokens - 1)."""

import numpy as np


def read(cell):
    values = cell.facts["tpot_s"]
    return float(np.percentile(values, 95)) if values else None
