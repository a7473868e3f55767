"""Host time one refresh of the subspace service spends blocked on the
device: the mean, over the window's refreshes, of the summed durations of
its ``repro.service.refresh.wait_count`` and ``.wait_jump`` spans (the
row count it reads before the solve and the basis jump it reads after),
grouped by the spans' ``refresh`` id.  Layer: service refresh
(``repro.stream.service``).  Moves ``refresh_lag_ms``."""

from bench import program_spans as ps

WAITS = ("repro.service.refresh.wait_count",
         "repro.service.refresh.wait_jump")


def read(ctx):
    spans = ps.window_spans(ctx) or ()
    # Refreshes whose whole span lies in the window; their waits lie in it.
    ids = {s.id("refresh") for s in ps.named(spans, "repro.service.refresh")}
    waits = ps.by_id([s for s in spans if s.name in WAITS], "refresh")
    totals = [sum(s.dur_ns for s in waits.get(i, ())) for i in ids]
    if not totals:
        return None
    return sum(totals) / len(totals) * 1e-6
