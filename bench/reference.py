"""The plain reference the benchmark compares the program with.

It follows the configuration as stated, in straightforward ``jax.numpy``
on the device and ``numpy`` float64 on the host, and imports nothing of
the program:

* covariance: X^T X / n of each shard's rows, at the precision the
  configuration states for it;
* local solve: blocked subspace iteration (matmul and QR only) from the
  configured start, at the solve's precision.  Its span is the answer; the
  program's closing Rayleigh-Ritz rotation does not move a span;
* aggregation: Algorithm 2 of arXiv 2009.02436 (Procrustes-fixed average
  against the first shard's basis, polar factor by SVD, re-orthonormalized
  by QR), in float64 on the host;
* distance: sin of the largest principal angle between two spans,
  in float64 on the host.

Each precision is one of ``default`` (one bfloat16 pass, float32 sums),
``high`` (three passes), ``highest`` (float32), or ``bfloat16`` (bfloat16
inputs and outputs): the last two steps down are the control's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MATMUL_PRECISIONS = ("default", "high", "highest")


def _check_precision(precision: str) -> None:
    if precision not in MATMUL_PRECISIONS + ("bfloat16",):
        raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("precision",))
def _gram(x: jax.Array, precision: str) -> jax.Array:
    if precision == "bfloat16":
        xb = x.astype(jnp.bfloat16)
        return jnp.matmul(xb.T, xb, preferred_element_type=jnp.bfloat16)
    return jnp.matmul(x.T, x, precision=precision,
                      preferred_element_type=jnp.float32)


def gram(x: jax.Array, precision: str) -> jax.Array:
    """X^T X (float32) of rows x (n, d) at ``precision``.  A bfloat16
    result is widened outside the jitted product, where no compiler pass
    can fold the rounding away."""
    _check_precision(precision)
    return _gram(x, precision).astype(jnp.float32)


def covariance(x: jax.Array, precision: str) -> jax.Array:
    return gram(x, precision) / x.shape[0]


@functools.partial(jax.jit, static_argnames=("r", "iters", "start_key"))
def _span(c: jax.Array, r: int, iters: int, start_key: int) -> jax.Array:
    v0 = jax.random.normal(jax.random.PRNGKey(start_key), (c.shape[0], r),
                           dtype=c.dtype)
    q, _ = jnp.linalg.qr(v0)
    return jax.lax.fori_loop(0, iters, lambda _, q: jnp.linalg.qr(c @ q)[0], q)


def leading_span(c: jax.Array, r: int, *, iters: int, start_key: int,
                 precision: str) -> jax.Array:
    """An orthonormal (d, r) basis of the subspace iteration's span on the
    symmetric c, every product at ``precision`` (``bfloat16`` means
    ``default`` here: the solve has no narrower product)."""
    _check_precision(precision)
    with jax.default_matmul_precision(
            "default" if precision == "bfloat16" else precision):
        return _span(c, r, iters, start_key)


@functools.partial(jax.jit, static_argnames=("precision",))
def _project(q: jax.Array, basis: jax.Array, precision: str) -> jax.Array:
    if precision == "bfloat16":
        return jnp.matmul(q.astype(jnp.bfloat16), basis.astype(jnp.bfloat16),
                          preferred_element_type=jnp.bfloat16)
    return jnp.matmul(q, basis, precision=precision)


def project(q: jax.Array, basis: jax.Array, precision: str) -> jax.Array:
    """q @ basis on the device at ``precision`` (the control's query path)."""
    _check_precision(precision)
    return _project(q, basis, precision).astype(jnp.float32)


def to_host(v) -> np.ndarray:
    return np.asarray(jax.device_get(v), dtype=np.float64)


def orthonormal(v: np.ndarray) -> np.ndarray:
    q, _ = np.linalg.qr(np.asarray(v, dtype=np.float64))
    return q


def polar(a: np.ndarray) -> np.ndarray:
    u, _, vt = np.linalg.svd(a)
    return u @ vt


def procrustes_average(bases, n_iter: int) -> np.ndarray:
    """Algorithm 1 (n_iter=1) / 2 (n_iter>1) over float64 local bases:
    align each to the reference by its polar factor, average, and
    re-orthonormalize; the first shard's basis is the first reference."""
    vs = [orthonormal(v) for v in bases]
    ref = vs[0]
    for _ in range(max(n_iter, 1)):
        vbar = sum(v @ polar(v.T @ ref) for v in vs) / len(vs)
        ref = orthonormal(vbar)
    return ref


def sin_theta(v, w) -> float:
    """sin of the largest principal angle between span(v) and span(w)."""
    v, w = orthonormal(v), orthonormal(w)
    return float(np.linalg.norm(v - w @ (w.T @ v), 2))


def bf16_round(x) -> np.ndarray:
    """x rounded to bfloat16 as a default-precision product rounds its
    operands, returned in float64."""
    return np.asarray(jax.device_get(jnp.asarray(x).astype(jnp.bfloat16)),
                      dtype=np.float64)


def projection(q, basis, precision: str) -> np.ndarray:
    """q @ basis in float64 from operands as the stated precision sees
    them: rounded to bfloat16 for ``default`` on a TPU (one bfloat16 pass;
    the CPU's default product is float32), exact otherwise."""
    if precision == "default" and jax.default_backend() == "tpu":
        return bf16_round(q) @ bf16_round(basis)
    if precision == "default":
        return to_host(q) @ to_host(basis)
    if precision in ("high", "highest"):
        return to_host(q) @ to_host(basis)
    raise ValueError(f"no float64 projection reference at {precision!r}")


def rel_err(got, want: np.ndarray) -> float:
    got = np.asarray(jax.device_get(got), dtype=np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
