"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips.  Layer: device.  Moves ``pca_job_s``."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.counters["busy_s"] / ctx.counters["trace_window_s"])
