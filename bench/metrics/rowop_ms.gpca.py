"""Device time per job and chip of the row form's local solve: the
subspace iteration's ``while`` loop on Xᵀ(XQ)/n.

The device trace carries no name stack, so the matcher finds the loop by
its carry, as ``solve_ms.pca`` finds the covariance form's: the (d, r)
float32 iterate beside the (n, d) int8 genotype counts it multiplies
(``repro.core.rowop`` under ``repro.core.subspace.subspace_iteration``;
the loop's interval spans its body).  The first QR and the closing
Rayleigh-Ritz step, one more pass over the counts, fall outside the loop.
Layer: local solve, row form.  Moves ``pca_job_s``."""

from bench import trace_reduce as tr


def matcher(n: int, d: int, r: int) -> tr.Matcher:
    # The carry's tuple type runs up to the op's first "="; the two
    # operands may come in either order.
    return tr.Matcher(rf"^%while[.\d]* = \((?=[^=]*?f32\[{d},{r}\])"
                      rf"(?=[^=]*?s8\[{n},{d}\])")


def seconds_per_job(ctx):
    """The loop's device seconds per job and chip, or None."""
    jobs = ctx.counters.get("jobs")
    if ctx.trace is None or not jobs:
        return None
    c = ctx.cfg
    s = tr.device_op_seconds(ctx.trace,
                             matcher(c["rows_per_shard"], c["d"], c["r"]),
                             *ctx.window_ns, ctx.counters["trace_devices"])
    if s is None:
        return None
    return s / (ctx.chips * jobs)


def read(ctx):
    s = seconds_per_job(ctx)
    return None if s is None else s * 1e3
