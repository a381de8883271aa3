"""ttft_p95_s: 95th percentile, over every request that arrived in the
window, of its first token's time less the time it was due (open loop). A
request that never produced a token counts at the end of the drain."""

import numpy as np


def read(cell):
    values = cell.facts["ttft_s"]
    return float(np.percentile(values, 95)) if values else None
