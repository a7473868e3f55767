"""95th percentile of how late the query generator dispatched each batch
after it was due (host clock).  Layer: load generator.  Moves
``query_p95_ms``: a late generator would hide latency, not add it."""

import numpy as np


def read(ctx):
    late = ctx.counters.get("gen_late_s")
    if not late:
        return None
    return float(np.percentile(late, 95)) * 1e3
