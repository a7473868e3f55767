"""The row form's local solve against its roofline: the least time one
chip needs for one site's subspace iteration over the loop's device time
per job and chip (``rowop_ms.gpca``).

The least time counts the work the layer needs, not what an
implementation does: per iteration X q and Xᵀ z, 4 n d r FLOP at the
chip's bfloat16 peak, or the (n, d) int8 counts read once, n d bytes, at
its memory bandwidth, whichever is longer, times the iterations.  At
UK Biobank's widths the bytes bound it: 0.100 s per job on a v5e.
Layer: local solve, row form.  Moves ``pca_job_s``."""

from bench import harness
from bench import trace_reduce as tr

_rowop = harness.metric_reader("rowop_ms.gpca")


def least_time_s(n: int, d: int, r: int, iters: int, peaks: dict):
    return tr.least_time_s(iters * 4 * n * d * r, iters * n * d,
                           peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])


def read(ctx):
    if ctx.peaks is None:
        return None
    s = _rowop.seconds_per_job(ctx)
    if s is None:
        return None
    c = ctx.cfg
    least, _ = least_time_s(c["rows_per_shard"], c["d"], c["r"],
                            c["solver_iters"], ctx.peaks)
    return 100.0 * tr.roofline_share(least, s)
