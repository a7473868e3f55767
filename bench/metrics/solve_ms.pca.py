"""Device time per job and chip of the local solve's subspace iteration.

The device trace carries no name stack, so the matcher finds the solve by
its shape: the ``while`` loop whose carry holds the (d, r) iterate and the
(d, d) covariance it multiplies (``repro.core.subspace.subspace_iteration``;
the loop's interval spans its body).  Its closing Rayleigh-Ritz step and
first QR, about 1 ms of about 30 on a v5e, fall outside the loop.  Layer:
local solve.  Moves ``pca_job_s``."""

from bench import trace_reduce as tr


def matcher(d: int, r: int) -> tr.Matcher:
    # The carry's tuple type runs up to the op's first "=" (its layouts hold
    # parentheses, so nothing narrower bounds it).
    return tr.Matcher(
        rf"^%while[.\d]* = \([^=]*?f32\[{d},{r}\][^=]*?f32\[{d},{d}\]")


def read(ctx):
    jobs = ctx.counters.get("jobs")
    if ctx.trace is None or not jobs:
        return None
    s = tr.device_op_seconds(ctx.trace, matcher(ctx.cfg["d"], ctx.cfg["r"]),
                             *ctx.window_ns, ctx.counters["trace_devices"])
    if s is None:
        return None
    return s / (ctx.chips * jobs) * 1e3
