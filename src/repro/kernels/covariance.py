"""Pallas TPU kernel: tiled Gram matrix ``X^T X`` (local covariance hot spot).

Distributed PCA's per-machine work is dominated by forming the local
empirical covariance — a rank-n Gram update.  On TPU this is an MXU tiling
problem: stream (bn, bd) tiles of X through VMEM and accumulate f32
(bd, bd) output tiles.

Tiling:
  grid = (T(T+1)/2, n/bn) for T = d/bd: the first axis enumerates only the
  upper-triangle output tiles (i <= j), through two scalar-prefetched
  tables t -> i and t -> j that the ``BlockSpec`` index maps read, so a
  lower tile costs neither a DMA nor a grid step.  The last grid axis is
  sequential on TPU, so the output tile accumulates across the n-loop,
  128 rows per product.  Both operand tiles are VMEM blocks of X;
  accumulation is f32 regardless of input dtype.  The wrapper mirrors the
  upper triangle into the lower one with a select (the kernel never
  writes the lower tiles).

Blocks come from the shape (``gram_blocks``): each grid step moves
2·bn·bd·itemsize bytes for 2·bn·bd² FLOP, so a wider bd raises the work
per byte and cuts the steps, while a (bd, bd) diagonal tile computes its
lower half for nothing.  The VMEM a choice needs (double-buffered operand
and output tiles, the product and the f32 operand copies) is stated by
``gram_vmem_bytes`` and held under ``VMEM_BUDGET``; the ``pallas_call``
asks for that much scoped VMEM.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "gram", "gram_blocks", "gram_vmem_bytes", "GramBlocks", "VMEM_BUDGET",
]

# Scoped VMEM the kernel may ask for: half of a v5e core's 128 MiB.
VMEM_BUDGET = 64 * 2**20
# The default blocks, from a sweep of bn in 128..1024 and bd in 512..2048
# at a 65,536 x 8192 f32 shard on a TPU v5e (PERF.md): bd=1024 balances
# the diagonal tiles' wasted half against the operand bytes, and a deeper
# bn cuts the steps.
_BN, _BD = 1024, 1024
# Rows per product in the kernel body: a (bn, bd) tile is summed into the
# output 128 rows at a time, so G's float32 sums run in the same order
# whatever bn is (bit for bit; at bn=1024 also 4% faster than one product).
_ROWS = 128
# Room for Mosaic's own scratch on top of the stated buffers.
_VMEM_HEADROOM = 4 * 2**20


class GramBlocks(NamedTuple):
    bn: int
    bd: int
    tiles: int  # output tiles launched: the upper triangle, T(T+1)/2
    full_tiles: int  # output tiles of the full square, T²
    vmem_bytes: int  # ``gram_vmem_bytes(bn, bd, itemsize)``


def gram_vmem_bytes(bn: int, bd: int, itemsize: int) -> int:
    """VMEM one grid step holds: two (bn, bd) operand tiles and the f32
    (bd, bd) output tile, each double-buffered by the pipeline, plus an
    f32 product and f32 copies of the operands (bounded by the tile)."""
    return (2 * 2 * bn * bd * itemsize + 2 * bd * bd * 4
            + bd * bd * 4 + 2 * bn * bd * 4)


def gram_blocks(
    n: int, d: int, itemsize: int, *, bn: int | None = None,
    bd: int | None = None,
) -> GramBlocks:
    """Blocks of the Gram kernel for (n, d) rows of ``itemsize`` bytes.

    ``bd`` defaults to 1024 and ``bn`` to 1024, halved while the step's
    VMEM exceeds ``VMEM_BUDGET``; either is clamped to the (small) shape.
    n and d are then padded up to multiples of the blocks.
    """
    bd = _BD if bd is None else bd
    if bn is None:
        bn = _BN
        while gram_vmem_bytes(bn, bd, itemsize) > VMEM_BUDGET and bn > 8:
            bn //= 2
    bn = min(bn, max(8, n))
    bd = min(bd, max(8, d))
    t = -(-d // bd)
    return GramBlocks(bn, bd, t * (t + 1) // 2, t * t,
                      gram_vmem_bytes(bn, bd, itemsize))


def _gram_kernel(ti, tj, x_i, x_j, out, *, rows: int):
    del ti, tj  # consumed by the index maps

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out[...] = jnp.zeros_like(out)

    for s in range(x_i.shape[0] // rows):
        r = pl.ds(s * rows, rows)
        out[...] += jnp.dot(
            x_i[r, :].T.astype(jnp.float32),
            x_j[r, :].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )


@functools.partial(jax.jit, static_argnames=("bn", "bd", "interpret"))
def gram(
    x: jax.Array,
    *,
    bn: int | None = None,
    bd: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``X^T X`` for x of shape (n, d); f32 output, exactly symmetric.

    Pads n and d up to the block sizes (zero rows/cols contribute nothing to
    the Gram product, so padding is exact).  ``bn``/``bd`` override the
    blocks ``gram_blocks`` picks from the shape.
    """
    n, d = x.shape
    blocks = gram_blocks(n, d, x.dtype.itemsize, bn=bn, bd=bd)
    bn, bd = blocks.bn, blocks.bd
    n_pad = (-n) % bn
    d_pad = (-d) % bd
    if n_pad or d_pad:
        x = jnp.pad(x, ((0, n_pad), (0, d_pad)))
    np_, dp = x.shape
    ti, tj = np.triu_indices(dp // bd)
    out = pl.pallas_call(
        functools.partial(
            _gram_kernel, rows=_ROWS if bn % _ROWS == 0 else bn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(blocks.tiles, np_ // bn),
            in_specs=[
                pl.BlockSpec((bn, bd), lambda t, k, ti, tj: (k, ti[t])),
                pl.BlockSpec((bn, bd), lambda t, k, ti, tj: (k, tj[t])),
            ],
            out_specs=pl.BlockSpec(
                (bd, bd), lambda t, k, ti, tj: (ti[t], tj[t])),
        ),
        out_shape=jax.ShapeDtypeStruct((dp, dp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=blocks.vmem_bytes + _VMEM_HEADROOM,
        ),
        interpret=interpret,
        name="gram",
    )(jnp.asarray(ti, jnp.int32), jnp.asarray(tj, jnp.int32), x, x)
    out = out[:d, :d]
    # The lower tiles were never written: select the upper triangle and
    # its transpose (a select, so whatever they hold never reaches G).
    i = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    return jnp.where(i <= j, out, out.T)
