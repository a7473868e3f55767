"""Share of the traced window in which no operation ran on the device.
Layer: device.  Moves ``query_p95_ms``."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.counters["busy_s"] / ctx.counters["trace_window_s"])
