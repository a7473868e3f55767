"""Device time per refresh of the service's refresh program (the XLA
module of ``SubspaceService.refresh_fn``: local solve and alignment).
Layer: service refresh (``repro.stream.service``).  Moves
``refresh_lag_ms``."""

from bench import trace_reduce as tr

# ``refresh_fn`` jits a shard_map of ``shard_fn``: its module is
# ``jit_shard_fn(<id>)`` on the trace's module line.
MODULE = tr.Matcher(r"^jit_shard_fn\(")


def read(ctx):
    n = ctx.counters.get("refreshes")
    if ctx.trace is None or not n:
        return None
    lo, hi = ctx.window_ns
    ns = sum(tr.matched_ns(ctx.trace.modules(d), MODULE, lo, hi)
             for d in ctx.counters["trace_devices"])
    if ns <= 0:
        return None
    return ns * 1e-9 / (ctx.chips * n) * 1e3
