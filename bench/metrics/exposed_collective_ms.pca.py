"""Device time per job and chip in which a collective (all-gather,
all-reduce, collective-permute, reduce-scatter, all-to-all) ran and no
other operation did.  Layer: collectives (``repro.comm``,
``procrustes_average_collective``).  Moves ``pca_job_s``."""

from bench import trace_reduce as tr

COLLECTIVE = tr.Matcher(
    r"^(all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all)",
    fields=("short",))


def read(ctx):
    jobs = ctx.counters.get("jobs")
    if ctx.trace is None or not jobs:
        return None
    lo, hi = ctx.window_ns
    devs = ctx.counters["trace_devices"]
    if not any(COLLECTIVE(e) for d in devs for e in ctx.trace.ops(d)):
        return None
    ns = sum(tr.exposed_ns(ctx.trace.ops(d), COLLECTIVE, lo, hi) for d in devs)
    return ns * 1e-9 / (len(devs) * jobs) * 1e3
