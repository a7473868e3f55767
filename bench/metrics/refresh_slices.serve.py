"""Slices per refresh of the subspace service: the mean, over the window's
refreshes, of the number of ``repro.service.refresh.slice`` spans that
carry the refresh's ``refresh`` id.  ``SubspaceService.refresh``
dispatches its local solve as that many programs, one queued behind the
running one at most, so a query waits behind two slices at most and not
behind the whole solve.  A program without these spans (one that runs
the solve as one program) reads as nothing.  Layer: service refresh
(``repro.stream.service``).  Moves ``query_p95_ms``."""

from bench import program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx) or ()
    # Refreshes whose whole span lies in the window; their slices lie in it.
    ids = {s.id("refresh") for s in ps.named(spans, "repro.service.refresh")}
    slices = ps.by_id(ps.named(spans, "repro.service.refresh.slice"),
                      "refresh")
    counts = [len(slices[i]) for i in ids if i in slices]
    if not counts:
        return None
    return sum(counts) / len(counts)
