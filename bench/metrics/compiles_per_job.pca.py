"""Executables JAX builds per PCA job inside the window, compiled or
fetched from the persistent compile cache (``jax.monitoring`` events): the
entry point's per-call cost.  Layer: entry point.  Moves ``pca_job_s``."""


def read(ctx):
    jobs = ctx.counters.get("jobs")
    if not jobs:
        return None
    return ctx.counters["builds_in_window"] / jobs
