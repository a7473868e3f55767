"""The Gram kernel's share of its roofline: the least time one chip needs
for one shard's covariance over the kernel's device time per job and chip.

The least time counts the work the layer needs, not what an
implementation does: n d (d + 1) FLOP (one triangle with its diagonal) at
the chip's bfloat16 peak (the Gram runs at the default precision, one
bfloat16 pass), or n d 4 + d^2 4 bytes (the float32 rows read once, the
float32 covariance written once) at its memory bandwidth, whichever is
longer.  Layer: covariance (``repro.core.covariance``, Pallas
``kernels.covariance.gram``).  Moves ``pca_job_s``."""

from bench import trace_reduce as tr

# The Pallas kernel's custom call is named after its entry,
# ``repro.kernels.covariance.gram`` (``%gram.1 = f32[d,d] custom-call``);
# ``batched_gram`` of the aggregation is another kernel.
MATCH = tr.Matcher(r"^gram(\.\d+)?$", fields=("short",))


def least_time_s(n: int, d: int, peaks: dict):
    return tr.least_time_s(n * d * (d + 1), 4 * (n * d + d * d),
                           peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])


def read(ctx):
    jobs = ctx.counters.get("jobs")
    if ctx.trace is None or not jobs or ctx.peaks is None:
        return None
    s = tr.device_op_seconds(ctx.trace, MATCH, *ctx.window_ns,
                             ctx.counters["trace_devices"])
    if s is None:
        return None
    least, _ = least_time_s(ctx.cfg["rows_per_shard"], ctx.cfg["d"], ctx.peaks)
    return 100.0 * tr.roofline_share(least, s / (ctx.chips * jobs))
