"""How long a query waits for the device: the 95th percentile, over the
window's queries, of the start of the query's ``jit_project`` program on
the first device's module line less the start of its
``repro.service.project`` span on the host.

The trace does not say which program a span enqueued.  The device runs
programs in the order they were enqueued, so the spans, in the order they
start, take the ``jit_project`` modules in the order they start.  A v5e
trace puts the device's events up to a few milliseconds early against the
host's (a query's program on an idle device shows as starting 0.3-1.3 ms
before its span), so a module may start up to ``SKEW_NS`` before its
span; one that starts earlier belongs to no span here and is passed
over.  A query whose program starts after the window is left out.  Layer:
service query (``SubspaceService.project``).  Moves ``query_p95_ms``."""

import numpy as np

from bench import program_spans as ps
from bench import trace_reduce as tr

# ``SubspaceService.project`` runs ``jax.jit(project)``: ``jit_project(<id>)``.
MODULE = tr.Matcher(r"^jit_project\(")
SKEW_NS = 5e6


def waits_ns(spans, module_starts, hi):
    """Each span's wait for its module, in order, for the spans whose
    module starts by ``hi``."""
    starts = sorted(module_starts)
    out, j = [], 0
    for s in sorted(spans, key=lambda s: s.start_ns):
        while j < len(starts) and starts[j] < s.start_ns - SKEW_NS:
            j += 1
        if j == len(starts) or starts[j] > hi:
            break
        out.append(starts[j] - s.start_ns)
        j += 1
    return out


def read(ctx):
    spans = ps.named(ps.window_spans(ctx) or (), "repro.service.project")
    if not spans:
        return None
    dev = ctx.counters["trace_devices"][0]
    starts = [m.start_ns for m in ctx.trace.modules(dev) if MODULE(m)]
    waits = waits_ns(spans, starts, ctx.window_ns[1])
    if not waits:
        return None
    return float(np.percentile(waits, 95)) * 1e-6
