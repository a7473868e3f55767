"""Host time of one ``distributed_pca`` call: the mean duration of the
window's ``repro.pca`` spans (plan resolution, building the program, its
trace, lowering, executable fetch and enqueue; the device work runs after
it).  Layer: entry point.  Moves ``pca_job_s``."""

from bench import program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    calls = ps.named(spans or (), "repro.pca")
    if not calls:
        return None
    return sum(s.dur_ns for s in calls) / len(calls) * 1e-6
