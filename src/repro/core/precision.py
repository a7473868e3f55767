"""Full-precision f32 products for the estimator's numerics.

A TPU runs an f32 matmul at default precision as one bf16 pass: both
operands are rounded to 8 bits of mantissa.  A (d, r) basis rounded so
moves its span by ~2^-9 ≈ 2e-3 in subspace distance, and by a different
amount for any rotation of the same basis, so two computations of one
estimator that differ only in rounding land ~1e-3 apart.  The local
subspace iteration, the aggregation rounds and the subspace distances
therefore trace every product at HIGHEST.  Their operands are (d, r) and (r, r): the extra
passes cost little next to the covariance, whose (n, d) Gram stays at the
default precision (rows rounded to bf16 move it by ~1e-4 relative, the
same for every path).
"""

from __future__ import annotations

import functools

import jax

__all__ = ["highest_precision"]


def highest_precision(fn):
    """Trace ``fn`` with every matmul it issues at HIGHEST precision,
    Pallas kernel bodies included; explicit ``precision=`` arguments keep
    theirs."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped
