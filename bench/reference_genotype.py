"""The plain reference for genotype PCA, which the benchmark compares the
program with.  It imports nothing of the program.

For each site's (n, d) int8 counts C, in straightforward ``jax.numpy`` on
the device, one block of rows at a time so that it fits:

* standardization: Patterson, Price & Reich (2006) eq. 3, from exact
  integer column sums: mu_j = sum_i C_ij / n,
  p_j = (1 + sum_i C_ij) / (2 + 2n), X_ij = (C_ij - mu_j) / sqrt(p_j (1 - p_j));
* local solve: subspace iteration (matmul and QR only) on X^T (X Q) / n
  from the configured start, each block's X formed explicitly in float32;
  its span is the answer.  The product X Q sums d = 147,604 terms per
  entry; one float32 accumulation that long rounds about as much as the
  program's own products do, so the reference cuts it into ``CHUNK``-term
  pieces whose partial products it sums pairwise;
* aggregation: Algorithm 2 over the sites' spans in float64 on the host
  (``bench/reference.py``).

The products with the rows run at the stated precision.  ``highest`` is a
float32 product.  ``high`` and ``default`` are written out as the chip
computes them, so that they mean the same on any backend: each float32
operand split into bfloat16 parts, x = a + b + ... with a = bf16(x) and
b = bf16(x - a); ``high`` sums a_x a_q + a_x b_q + b_x a_q, ``default`` is
a_x a_q, each part's product exact in float32.  The parts are rounded with
``reduce_precision``, which no compiler pass folds away (a round trip
through a bfloat16 array may be).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import reference as ref

PRECISIONS = ("default", "high", "highest")
ROW_BLOCK = 1024
CHUNK = 2048


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def dot(x: jax.Array, y: jax.Array, precision: str) -> jax.Array:
    """x @ y of float32 operands at ``precision`` (batched, as
    ``jnp.matmul``, over leading axes)."""
    def exact(a, b):  # bfloat16 values held in float32: exact products
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    if precision == "highest":
        return exact(x, y)
    xa, ya = _bf16(x), _bf16(y)
    if precision == "default":
        return exact(xa, ya)
    if precision == "high":
        return (exact(xa, ya) + exact(xa, _bf16(y - ya))
                + exact(_bf16(x - xa), ya))
    raise ValueError(f"unknown precision {precision!r}")


def _pairwise_sum(p: jax.Array) -> jax.Array:
    """The sum over the leading axis as a balanced tree of additions."""
    while p.shape[0] > 1:
        if p.shape[0] % 2:
            p = jnp.concatenate([p, jnp.zeros_like(p[:1])])
        half = p.shape[0] // 2
        p = p[:half] + p[half:]
    return p[0]


def long_dot(x: jax.Array, y: jax.Array, precision: str,
             chunk: int = CHUNK) -> jax.Array:
    """x @ y at ``precision``, the contraction cut into ``chunk``-term
    pieces (the last padded with zeros) whose products are summed
    pairwise."""
    k = x.shape[1]
    if k <= chunk:
        return dot(x, y, precision)
    pieces = -(-k // chunk)
    pad = pieces * chunk - k
    xs = jnp.pad(x, ((0, 0), (0, pad))).reshape(x.shape[0], pieces, chunk)
    ys = jnp.pad(y, ((0, pad), (0, 0))).reshape(pieces, chunk, y.shape[1])
    return _pairwise_sum(dot(xs.transpose(1, 0, 2), ys, precision))


def _blocks(n: int, block: int):
    """(start, size) of each row block: whole blocks, then the rest."""
    whole = [(i * block, block) for i in range(n // block)]
    return whole + ([(n - n % block, n % block)] if n % block else [])


def moments(c: jax.Array, block: int = ROW_BLOCK):
    """(mu, sqrt(p (1 - p))) of eq. 3 from exact int32 column sums."""
    n = c.shape[0]
    s = jnp.zeros((c.shape[1],), jnp.int32)
    for start, size in _blocks(n, block):
        s = s + jnp.sum(jax.lax.dynamic_slice_in_dim(c, start, size)
                        .astype(jnp.int32), axis=0)
    s = s.astype(jnp.float32)
    p = (1.0 + s) / (2.0 + 2.0 * n)
    return s / n, jnp.sqrt(p * (1.0 - p))


@functools.partial(jax.jit, static_argnames=("r", "iters", "start_key",
                                             "precision", "block"))
def _span(c, r, iters, start_key, precision, block):
    n, d = c.shape
    block = min(block, n)
    mu, sd = moments(c, block)

    def step(_, q):
        def add(start, size, w):
            x = (jax.lax.dynamic_slice_in_dim(c, start, size)
                 .astype(jnp.float32) - mu) / sd
            return w + dot(x.T, long_dot(x, q, precision), precision)

        # One row block at a time, in a loop so that only one block's
        # float32 X and its chunks are live at once; then the rest.
        whole = n // block
        w = jax.lax.fori_loop(0, whole, lambda i, w: add(i * block, block, w),
                              jnp.zeros((d, r), jnp.float32))
        if n % block:
            w = add(whole * block, n % block, w)
        return jnp.linalg.qr(w / n)[0]

    # Everything besides the products with the rows (QR) in float32.
    with jax.default_matmul_precision("highest"):
        q0 = jax.random.normal(jax.random.PRNGKey(start_key), (d, r),
                               jnp.float32)
        q = jnp.linalg.qr(q0)[0]
        return jax.lax.fori_loop(0, iters, step, q)


def leading_span(c: jax.Array, r: int, *, iters: int, start_key: int,
                 precision: str, block: int = ROW_BLOCK) -> jax.Array:
    """An orthonormal (d, r) basis of the subspace iteration's span on one
    site's standardized rows, the products with the rows at
    ``precision``."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    return _span(c, r, iters, start_key, precision, block)


def reference_basis(rows: jax.Array, cfg: dict, precision: dict):
    """Algorithm 2's float64 (d, r) span over the sites of one job's rows:
    each shard (site) standardized and solved on its own device."""
    shards = sorted(rows.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    local = []
    for s in shards:
        v = leading_span(s.data, cfg["r"], iters=cfg["solver_iters"],
                         start_key=cfg["solver_start_key"],
                         precision=precision["rows"])
        local.append(ref.to_host(v))
        del v
    return ref.procrustes_average(local, cfg["n_iter"])
