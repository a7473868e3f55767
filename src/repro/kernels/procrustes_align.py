"""Pallas TPU kernels for the Procrustes-fixing aggregation stages.

Algorithm 1's coordinator work splits into three stages:

  1. Gram stage   G_i = V_i^T @ V_ref           (m tall-skinny matmuls)
  2. polar stage  Z_i = polar(G_i)              (r x r orthogonal factor)
  3. Apply stage  V_bar = (1/m) sum_i V_i @ Z_i (m rank-r updates)

Stages 1 and 3 stream the (m, d, r) stack of local bases through VMEM once
each; both are implemented here with explicit BlockSpec tiling.  ``r`` is
expected MXU-sub-tile (r <= 128): blocks keep the full r extent and tile d.

The polar stage has two homes:

  * ``batched_gram`` emits the raw Gram stack and the host graph computes
    ``Z_i = U_i W_i^T`` from an XLA SVD (latency-bound, no MXU win — the
    ``polar="svd"`` path, three dispatches per round).
  * ``batched_gram_polar`` fuses a Newton–Schulz polar iteration into the
    final d-step of each machine's sequential Gram accumulation: the r x r
    tile never leaves VMEM, the kernel emits Z_i directly, and the whole
    round is two kernel launches with no XLA compute in between (the
    ``polar="newton-schulz"`` path).  Each Newton–Schulz step is two r x r
    MXU matmuls; the XLA reference lives in
    ``repro.core.procrustes.newton_schulz_polar``.

VMEM budget per Gram-stage step (bk=2048, r=128, f32):
  v block + ref block         2 * bk*r*4  = 2.0 MiB
  out tile (G_i / Z_i)            r*r*4   = 64 KiB
  NS temporaries (X^T X, 3I)  2 * r*r*4   = 128 KiB
i.e. the fusion adds <200 KiB to the 2 MiB streaming budget — far under
the 16 MiB/core VMEM envelope, so ``bk`` need not shrink.

Newton–Schulz iteration count: ``ns_iters`` defaults to 24
(``repro.core.procrustes.DEFAULT_NS_ITERS``), sized as
``log_1.5(||G||_F / sigma_min(G)) + ~5`` — enough for cond(G)*sqrt(r) up
to ~1e3.  Aggregation Grams are near-orthogonal (G ~ I + noise) and need
only ~8 steps; raise ``ns_iters`` only for nearly rank-deficient stacks
(e.g. adversarially misaligned bases with tiny principal cosines).

These kernels are the ``backend="pallas"`` path of the public aggregation
API — ``repro.core.eigenspace.procrustes_fix_average`` /
``iterative_refinement`` and the ``repro.core.distributed`` collectives
dispatch here (compiled on TPU, interpret mode elsewhere; "auto" resolves
via ``repro.kernels.ops.resolve_backend``).  All kernels accept ragged
extents: d is padded to the block size and trimmed on the way out, and any
m >= 1 / r >= 1 works (tests/test_kernels_ragged.py sweeps the degenerate
shapes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "batched_gram",
    "batched_gram_polar",
    "align_average",
    "fused_round",
    "fused_ring_round",
    "fused_ring_vmem_bytes",
]

# Keep in sync with repro.core.procrustes.DEFAULT_NS_ITERS (not imported to
# keep the kernel package free of core dependencies).
_DEFAULT_NS_ITERS = 24


def _gram_accumulate(v, ref, out):
    out[...] += jnp.dot(
        v[0].T.astype(jnp.float32),
        ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )[None]


def _batched_gram_kernel(v, ref, out):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out[...] = jnp.zeros_like(out)

    _gram_accumulate(v, ref, out)


def _ns_polar_tile(g: jax.Array, ns_iters: int) -> jax.Array:
    """Newton–Schulz polar factor of an in-VMEM (r, r) f32 tile."""
    norm = jnp.sqrt(jnp.sum(g * g))
    x = g / jnp.maximum(norm, 1e-30)
    eye3 = 3.0 * jnp.eye(g.shape[-1], dtype=jnp.float32)
    for _ in range(ns_iters):
        xtx = jnp.dot(x.T, x, preferred_element_type=jnp.float32)
        x = 0.5 * jnp.dot(x, eye3 - xtx, preferred_element_type=jnp.float32)
    return x


def _batched_gram_polar_kernel(v, ref, out, *, nk: int, ns_iters: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out[...] = jnp.zeros_like(out)

    _gram_accumulate(v, ref, out)

    @pl.when(k == nk - 1)
    def _polar():
        # The Gram tile is complete; run Newton–Schulz on it in VMEM and
        # emit the orthogonal polar factor Z_i in place of G_i.
        out[...] = _ns_polar_tile(out[0], ns_iters)[None]


def _gram_stage_call(kernel, vs, ref, *, bk, interpret):
    """Shared (m, d/bk) grid launch for the Gram-stage kernels."""
    m, d, r = vs.shape
    bk = min(bk, max(8, d))
    d_pad = (-d) % bk
    if d_pad:
        vs = jnp.pad(vs, ((0, 0), (0, d_pad), (0, 0)))
        ref = jnp.pad(ref, ((0, d_pad), (0, 0)))
    dp = vs.shape[1]
    grid = (m, dp // bk)
    return pl.pallas_call(
        kernel(nk=dp // bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bk, r), lambda i, k: (i, k, 0)),
            pl.BlockSpec((bk, r), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, r), lambda i, k: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, r, r), jnp.float32),
        interpret=interpret,
    )(vs, ref)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def batched_gram(
    vs: jax.Array, ref: jax.Array, *, bk: int = 2048, interpret: bool = False
) -> jax.Array:
    """G_i = V_i^T @ ref for a stack vs (m, d, r) and reference (d, r).

    Returns (m, r, r) f32.  Grid: (m, d/bk); the d-loop is the sequential
    (minor) dimension, accumulating each machine's Gram tile in VMEM.
    """
    return _gram_stage_call(
        lambda nk: _batched_gram_kernel, vs, ref, bk=bk, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("bk", "ns_iters", "interpret"))
def batched_gram_polar(
    vs: jax.Array,
    ref: jax.Array,
    *,
    bk: int = 2048,
    ns_iters: int = _DEFAULT_NS_ITERS,
    interpret: bool = False,
) -> jax.Array:
    """Fused Gram + Newton–Schulz polar: Z_i = polar(V_i^T @ ref).

    Same tiling as ``batched_gram``; the final d-step of each machine's
    sequential accumulation runs ``ns_iters`` Newton–Schulz steps on the
    in-VMEM r x r tile and writes the orthogonal factor directly, so the
    SVD-free pipeline is two kernels total (this + ``align_average``).
    Returns (m, r, r) f32.
    """
    return _gram_stage_call(
        lambda nk: functools.partial(
            _batched_gram_polar_kernel, nk=nk, ns_iters=ns_iters
        ),
        vs, ref, bk=bk, interpret=interpret,
    )


def _align_average_kernel(v, z, out, *, m: int):
    i = pl.program_id(1)  # machine index (sequential minor dim)

    @pl.when(i == 0)
    def _init():
        out[...] = jnp.zeros_like(out)

    out[...] += jnp.dot(
        v[0].astype(jnp.float32),
        z[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == m - 1)
    def _finalize():
        out[...] = out[...] / m


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def align_average(
    vs: jax.Array, zs: jax.Array, *, bd: int = 2048, interpret: bool = False
) -> jax.Array:
    """(1/m) sum_i V_i @ Z_i for vs (m, d, r), zs (m, r, r) -> (d, r) f32.

    Grid: (d/bd, m); the machine loop is sequential, accumulating into the
    (bd, r) output tile, with the 1/m scale fused into the last step.
    """
    m, d, r = vs.shape
    bd = min(bd, max(8, d))
    d_pad = (-d) % bd
    if d_pad:
        vs = jnp.pad(vs, ((0, 0), (0, d_pad), (0, 0)))
    dp = vs.shape[1]
    grid = (dp // bd, m)
    out = pl.pallas_call(
        functools.partial(_align_average_kernel, m=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bd, r), lambda j, i: (i, j, 0)),
            pl.BlockSpec((1, r, r), lambda j, i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bd, r), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((dp, r), jnp.float32),
        interpret=interpret,
    )(vs, zs)
    return out[:d]


# ---------------------------------------------------------------------------
# Fused full-round kernel: Gram + NS polar + aligned-average + CholeskyQR2
# in a single pallas_call (the ``orth="cholesky-qr2"`` path).


def _masked_cholesky(a0, row, col, eps_floor=1e-30):
    """Lower Cholesky of an (r, r) f32 tile by masked rank-1 updates.

    Mosaic has no LAPACK primitives, so the factorization is r ``fori_loop``
    steps of vector ops: extract pivot/column k by iota masks, scale, and
    apply the rank-1 Schur update.  Also returns the minimum pivot seen (the
    breakdown signal for the shift guard).
    """
    r = a0.shape[-1]

    def body(k, carry):
        a, minpiv = carry
        akk = jnp.sum(jnp.where((row == k) & (col == k), a, 0.0))
        ck = jnp.sum(
            jnp.where((col == k) & (row >= k), a, 0.0), axis=1, keepdims=True
        )
        lk = ck * jax.lax.rsqrt(jnp.maximum(akk, eps_floor))
        schur = a - lk * jnp.swapaxes(lk, 0, 1)
        a = jnp.where(
            col == k, jnp.broadcast_to(lk, (r, r)), jnp.where(col > k, schur, a)
        )
        return a, jnp.minimum(minpiv, akk)

    a, minpiv = jax.lax.fori_loop(
        0, r, body, (a0, jnp.asarray(jnp.inf, jnp.float32))
    )
    return jnp.where(row >= col, a, 0.0), minpiv


def _cholqr_inverse_factor(s, *, pivot_c: float, shift_c: float):
    """W = R^-1 (upper) with R = chol(S) of an (r, r) f32 Gram tile.

    The CholeskyQR step Q = V̄ R^-1 then becomes one tall-skinny matmul per
    d-block.  Guard rule mirrors ``repro.core.orthonorm.cholqr_guard_coeffs``
    (not imported: the kernel package stays core-free): if any pivot falls
    below ``pivot_c * tr(S)``, refactor the shifted Gram
    ``S + shift_c * tr(S) * I``.  The inverse is exact in ceil(log2 r)
    matmuls: L = D (I + N) with N strictly lower (nilpotent), so
    L^-1 = (I - N)(I + N^2)(I + N^4)... D^-1.
    """
    r = s.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (r, r), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)
    eye = (row == col).astype(jnp.float32)
    tr = jnp.sum(s * eye)
    l0, minpiv = _masked_cholesky(s, row, col)
    # The 1e-30 floor keeps the all-zero degenerate tile finite (Q = 0);
    # it mirrors the XLA reference in repro.core.orthonorm.
    ls, _ = _masked_cholesky(s + (shift_c * tr + 1e-30) * eye, row, col)
    l = jnp.where(minpiv > pivot_c * tr, l0, ls)
    dinv = 1.0 / jnp.sum(jnp.where(row == col, l, 0.0), axis=1, keepdims=True)
    n = jnp.where(row > col, l * dinv, 0.0)
    x = eye - n
    pw = jnp.dot(n, n, preferred_element_type=jnp.float32)
    span = 2
    while span < r:
        x = jnp.dot(x, eye + pw, preferred_element_type=jnp.float32)
        pw = jnp.dot(pw, pw, preferred_element_type=jnp.float32)
        span *= 2
    linv = x * jnp.swapaxes(dinv, 0, 1)
    return jnp.swapaxes(linv, 0, 1)


# Slot names of the fused kernel's (4, r, r) stats buffer.
_S_ACC1, _S_ACC2, _W1, _W2 = 0, 1, 2, 3


def _fused_round_kernel(
    v, ref, out, gz, stats, vbar, *,
    nk: int, m: int, ns_iters: int, pivot_c: float, shift_c: float,
):
    """One Algorithm-1 round in a single launch; see ``fused_round``.

    Grid (4, nk, m), all phases d-block-major / machine-minor:

      phase 0  accumulate every machine's Gram tile  G_i += V_i[j]^T ref[j]
      phase 1  NS-polarize G_i -> Z_i in place (at each machine's first
               step), stream V̄[j] = (1/m) sum_i V_i[j] Z_i, accumulate
               S1 += V̄[j]^T V̄[j]; at the last step W1 = chol(S1)^-1
      phase 2  re-stream V̄[j], Q1[j] = V̄[j] W1, S2 += Q1[j]^T Q1[j];
               at the last step W2 = chol(S2)^-1
      phase 3  re-stream V̄[j], emit Q[j] = (V̄[j] W1) W2

    V̄ is recomputed from the resident Z stack in phases 2/3 instead of
    being staged in HBM — the round costs 4 streams of ``vs`` instead of
    the two-launch path's 2, trading bandwidth for zero XLA round-trips
    (the launch-latency win; see DESIGN.md §3.2).  Phase 3 recomputes
    Q1 bitwise-identically to phase 2, so W2 corrects the orthogonality of
    the *measured* Q1, preserving the CholeskyQR2 error bound.
    """
    p = pl.program_id(0)
    j = pl.program_id(1)
    i = pl.program_id(2)
    mi = pl.ds(i, 1)
    vf = v[0].astype(jnp.float32)

    @pl.when(p == 0)
    def _gram():
        @pl.when(j == 0)
        def _init():
            gz[mi] = jnp.zeros_like(gz[mi])

        gz[mi] += jnp.dot(
            vf.T, ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )[None]

    @pl.when((p == 1) & (j == 0))
    def _polarize():
        gz[mi] = _ns_polar_tile(gz[mi][0], ns_iters)[None]

    @pl.when(p > 0)
    def _stream_vbar():
        @pl.when(i == 0)
        def _init():
            vbar[...] = jnp.zeros_like(vbar)

        vbar[...] += jnp.dot(
            vf, gz[mi][0], preferred_element_type=jnp.float32
        )

    @pl.when((p == 1) & (i == m - 1))
    def _accum_s1():
        vb = vbar[...] / m
        c = jnp.dot(vb.T, vb, preferred_element_type=jnp.float32)
        stats[_S_ACC1] = jnp.where(j == 0, c, stats[_S_ACC1] + c)

        @pl.when(j == nk - 1)
        def _factor1():
            stats[_W1] = _cholqr_inverse_factor(
                stats[_S_ACC1], pivot_c=pivot_c, shift_c=shift_c
            )

    @pl.when((p == 2) & (i == m - 1))
    def _accum_s2():
        q1 = jnp.dot(
            vbar[...] / m, stats[_W1], preferred_element_type=jnp.float32
        )
        c = jnp.dot(q1.T, q1, preferred_element_type=jnp.float32)
        stats[_S_ACC2] = jnp.where(j == 0, c, stats[_S_ACC2] + c)

        @pl.when(j == nk - 1)
        def _factor2():
            stats[_W2] = _cholqr_inverse_factor(
                stats[_S_ACC2], pivot_c=pivot_c, shift_c=shift_c
            )

    @pl.when((p == 3) & (i == m - 1))
    def _emit():
        q1 = jnp.dot(
            vbar[...] / m, stats[_W1], preferred_element_type=jnp.float32
        )
        q = jnp.dot(q1, stats[_W2], preferred_element_type=jnp.float32)
        out[...] = q.astype(out.dtype)


def _fused_round_call(vs, ref, *, bk, ns_iters, pivot_c, shift_c, interpret):
    """Single-launch round on pre-padded inputs; returns padded (dp, r)."""
    m, dp, r = vs.shape
    nk = dp // bk
    grid = (4, nk, m)
    out, _, _, _ = pl.pallas_call(
        functools.partial(
            _fused_round_kernel, nk=nk, m=m, ns_iters=ns_iters,
            pivot_c=pivot_c, shift_c=shift_c,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bk, r), lambda p, j, i: (i, j, 0)),
            pl.BlockSpec((bk, r), lambda p, j, i: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bk, r), lambda p, j, i: (j, 0)),
            # Round-persistent state: constant block indices keep these
            # resident in VMEM for the whole grid (never re-fetched).
            pl.BlockSpec((m, r, r), lambda p, j, i: (0, 0, 0)),
            pl.BlockSpec((4, r, r), lambda p, j, i: (0, 0, 0)),
            pl.BlockSpec((bk, r), lambda p, j, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dp, r), vs.dtype),
            jax.ShapeDtypeStruct((m, r, r), jnp.float32),   # G_i -> Z_i
            jax.ShapeDtypeStruct((4, r, r), jnp.float32),   # S1/S2/W1/W2
            jax.ShapeDtypeStruct((bk, r), jnp.float32),     # V̄[j] tile
        ],
        interpret=interpret,
    )(vs, ref)
    return out


@functools.partial(
    jax.jit, static_argnames=("n_iter", "bk", "ns_iters", "interpret")
)
def fused_round(
    vs: jax.Array,
    ref: jax.Array,
    *,
    n_iter: int = 1,
    bk: int = 2048,
    ns_iters: int = _DEFAULT_NS_ITERS,
    interpret: bool = False,
) -> jax.Array:
    """``n_iter`` Algorithm-1 rounds, one pallas_call per round.

    Each round computes ``cholesky_qr2(mean_i(V_i @ polar(V_i^T @ ref)))``
    entirely in-kernel — Gram, Newton–Schulz polar, aligned average, and
    both CholeskyQR2 passes — so a round is exactly one launch with no XLA
    compute (no SVD, no Householder QR) anywhere.  Padding happens once
    outside the loop: round k's (dp, r) output feeds round k+1's reference
    directly, keeping the ``n_iter > 1`` loop XLA-free between launches.

    VMEM budget per step (bk=2048, r=128, m=16, f32, double-buffered v):
    v blocks ~2 MiB + ref/out/vbar tiles 3 MiB + Z stack 1 MiB + stats
    256 KiB — comfortably under the 16 MiB envelope.  The CholeskyQR guard
    coefficients mirror ``repro.core.orthonorm.cholqr_guard_coeffs``.

    Returns the (d, r) orthonormal round output in ``vs.dtype``.
    """
    m, d, r = vs.shape
    bk = min(bk, max(8, d))
    d_pad = (-d) % bk
    if d_pad:
        vs = jnp.pad(vs, ((0, 0), (0, d_pad), (0, 0)))
        ref = jnp.pad(ref, ((0, d_pad), (0, 0)))
    eps = float(jnp.finfo(jnp.float32).eps)
    # Keep in sync with repro.core.orthonorm.cholqr_guard_coeffs.
    pivot_c, shift_c = r * eps, 11.0 * (d + r + 1) * eps
    out = ref.astype(vs.dtype)
    for _ in range(max(n_iter, 1)):
        out = _fused_round_call(
            vs, out, bk=bk, ns_iters=ns_iters,
            pivot_c=pivot_c, shift_c=shift_c, interpret=interpret,
        )
    return out[:d]


# ---------------------------------------------------------------------------
# Fused *ring* round: the hop schedule driven by the kernel grid itself.
#
# ``fused_round`` above consumes an already-materialized (m, d, r) stack and
# pays 4 streams of it per round (V̄ is recomputed from the Z stack in
# phases 1-3).  The ring variant instead walks the m' hops directly: grid
# step (i, c) receives hop i's chunk c through the Pallas pipeline (the
# next chunk's copy is in flight while the MXU runs hop i's Gram and hop
# i-1's apply) and keeps it in a two-slot VMEM hop buffer until its apply
# one hop later.  The running V̄ is *fully VMEM-resident* for the whole
# round, so each hop's basis is read from HBM exactly once and the
# CholeskyQR2 tail re-streams V̄ from scratch memory, not from HBM:
# per-round traffic is ~(1 + 2/m) basis-streams instead of 4 (DESIGN.md
# §3.3).  Off-TPU the wire payloads are pre-gathered by
# ``repro.comm.ring.fused_ring_rounds`` and run under the Pallas
# interpreter.
#
# Every resident buffer is laid out (…, nc, chunk, r) and indexed only on
# its leading dims: Mosaic refuses a slice of the lane dim narrower than
# 128, which any r < 128 would otherwise need.


# Wire dtypes the in-kernel decoder understands, keyed by comm_bits (kept
# in sync with repro.comm.quantize.Codec.wire_dtype; not imported so the
# decode stays a static dtype dispatch).
_WIRE_BITS = {jnp.dtype(jnp.float32): 32,
              jnp.dtype(jnp.bfloat16): 16,
              jnp.dtype(jnp.int8): 8}


def fused_ring_vmem_bytes(d: int, r: int, wire_bits: int) -> float:
    """Resident working set of ``fused_ring_round`` as the planner prices
    it: three wire-width (d, r) hop buffers (two hop slots plus the
    pipeline's in-flight chunks) and the f32 V̄ / ref / out — the
    (3·wire + 12)·d·r bytes checked against the VMEM envelope."""
    return d * r * (3 * wire_bits / 8.0 + 3 * 4.0)


def _mosaic_vmem_limit(m: int, dp: int, chunk: int, r: int, wire: int) -> int:
    """Scoped-VMEM limit for the compiled ring kernel: its buffers with the
    lane dim padded to 128 (as Mosaic lays them out) and the pipeline's
    double-buffering of the resident blocks, plus headroom for in-kernel
    temporaries."""
    lanes = -(-r // 128) * 128
    f32 = dp * lanes * 4
    footprint = (
        2 * chunk * lanes * wire      # pipelined hop chunks in flight
        + 2 * dp * lanes * wire       # two hop slots
        + 5 * f32                     # V̄ + double-buffered ref and out
        + 2 * m * 8 * lanes * 4       # double-buffered (m, 1, r) scales
    )
    return int(footprint + 16 * 2**20)


def _fused_ring_round_kernel(
    x_in, ref, scales, out, hop, vbar, g, z, *,
    m: int, nc: int, ns_iters: int, pivot_c: float, shift_c: float,
    bits: int,
):
    """One ring-scheduled Algorithm-1 round; see ``fused_ring_round``.

    Grid (m+1, nc), hop-major / chunk-minor:

      Gram    g += dec(hop i chunk c)^T @ ref[c]; keep the chunk   (i < m)
      apply   V̄[c] += dec(hop i-1 chunk c) @ z                     (i >= 1)
      polar   z = NS(g) at c == nc-1, AFTER the apply consumed the old z
      tail    i == m, c == nc-1: V̄ /= m' and both CholeskyQR2 passes run
              on the resident V̄ — S2 is the Gram of the *measured* Q1, so
              the CholeskyQR2 bound is preserved.

    The wrapper pads d to a whole number of chunks; zero rows add exact
    zeros to every Gram and apply, and leave V̄'s Gram unchanged.
    """
    i = pl.program_id(0)
    c = pl.program_id(1)

    def dec(blk, h):
        x = blk.astype(jnp.float32)
        if bits == 8:
            x = x * scales[h]
        return x

    @pl.when((i == 0) & (c == 0))
    def _init():
        vbar[...] = jnp.zeros_like(vbar)

    @pl.when(i < m)
    def _hop_in():
        blk = x_in[0]
        hop[i % 2, c] = blk  # applied at hop i+1, once z is known
        contrib = jnp.dot(
            dec(blk, i).T, ref[c], preferred_element_type=jnp.float32
        )
        g[...] = jnp.where(c == 0, contrib, g[...] + contrib)

    @pl.when(i >= 1)
    def _apply_prev():
        x = dec(hop[(i - 1) % 2, c], i - 1)
        vbar[c] += jnp.dot(x, z[...], preferred_element_type=jnp.float32)

    @pl.when((i < m) & (c == nc - 1))
    def _polar():
        z[...] = _ns_polar_tile(g[...], ns_iters)

    @pl.when((i == m) & (c == nc - 1))
    def _tail():
        def gram_of(rows):
            def body(k, acc):
                q = rows(k)
                return acc + jnp.dot(q.T, q, preferred_element_type=jnp.float32)

            return jax.lax.fori_loop(0, nc, body, jnp.zeros_like(g[...]))

        vb = lambda k: vbar[k] / m
        w1 = _cholqr_inverse_factor(
            gram_of(vb), pivot_c=pivot_c, shift_c=shift_c
        )
        q1 = lambda k: jnp.dot(vb(k), w1, preferred_element_type=jnp.float32)
        w2 = _cholqr_inverse_factor(
            gram_of(q1), pivot_c=pivot_c, shift_c=shift_c
        )

        def emit(k, carry):
            out[k] = jnp.dot(q1(k), w2, preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, nc, emit, 0)


@functools.partial(
    jax.jit, static_argnames=("ring_chunk", "ns_iters", "interpret")
)
def fused_ring_round(
    vs: jax.Array,
    ref: jax.Array,
    scales: jax.Array | None = None,
    *,
    ring_chunk: int | None = None,
    ns_iters: int = _DEFAULT_NS_ITERS,
    interpret: bool = False,
) -> jax.Array:
    """One Algorithm-1 round over a staged ring of wire payloads, one launch.

    Args:
      vs: (m', d, r) stack of per-shard wire payloads in **wire dtype**
        (f32 / bf16 / int8, per ``repro.comm.quantize``), in canonical
        survivor order — hop h of the ring is row h.  The stack stays in
        HBM; the Pallas pipeline streams it one chunk ahead of the MXU.
      ref: (d, r) reference; accumulated against at f32.
      scales: (m', r) f32 per-column scales for the int8 tier (required
        iff ``vs.dtype == int8``).
      ring_chunk: rows per hop chunk — the DMA/compute overlap granularity,
        shared with the jnp schedule via ``repro.comm.ring.chunk_spans``
        (need not divide d: the stack is zero-padded to whole chunks).
      ns_iters / interpret: as in ``fused_round``.

    Returns the (d, r) **f32** orthonormal round output — f32 so round k's
    output feeds round k+1's ``ref`` operand with no XLA cast (or any
    other op) between launches.

    VMEM: ``fused_ring_vmem_bytes`` — ~12 MiB at (d=8192, r=64, f32
    wire).  Compiled, a working set over the planner's envelope
    (``repro.plan.roofline.TPU_V5E.vmem_cap_bytes``) raises ``ValueError``
    before Mosaic sees the kernel; the planner marks the same cell
    infeasible.
    """
    from repro.comm.ring import DEFAULT_RING_CHUNK, chunk_spans

    m, d, r = vs.shape
    bits = _WIRE_BITS.get(jnp.dtype(vs.dtype))
    if bits is None:
        raise ValueError(
            f"fused_ring_round expects a wire-dtype stack "
            f"(f32/bf16/int8), got {vs.dtype}"
        )
    if (scales is not None) != (bits == 8):
        raise ValueError(
            "scales must be passed iff the stack is int8 "
            f"(dtype={vs.dtype}, scales={'set' if scales is not None else None})"
        )
    if not interpret:
        from repro.plan.roofline import TPU_V5E

        need = fused_ring_vmem_bytes(d, r, bits)
        if need > TPU_V5E.vmem_cap_bytes:
            raise ValueError(
                f"fused_ring_round at d={d}, r={r} with a {bits}-bit wire "
                f"keeps {need / 2**20:.1f} MiB resident, over the "
                f"{TPU_V5E.vmem_cap_bytes / 2**20:.0f} MiB VMEM envelope; "
                "use another topology or a narrower wire"
            )
    chunk = DEFAULT_RING_CHUNK if ring_chunk is None else ring_chunk
    chunk = max(1, min(chunk, d))
    nc = len(chunk_spans(d, chunk))
    dp = nc * chunk
    if dp != d:
        vs = jnp.pad(vs, ((0, 0), (0, dp - d), (0, 0)))
        ref = jnp.pad(ref, ((0, dp - d), (0, 0)))
    eps = float(jnp.finfo(jnp.float32).eps)
    # Keep in sync with repro.core.orthonorm.cholqr_guard_coeffs.
    pivot_c, shift_c = r * eps, 11.0 * (d + r + 1) * eps
    if scales is None:
        scales = jnp.ones((m, r), jnp.float32)  # static no-op (bits != 8)
    resident = lambda i, c: (0, 0, 0)
    out = pl.pallas_call(
        functools.partial(
            _fused_ring_round_kernel, m=m, nc=nc, ns_iters=ns_iters,
            pivot_c=pivot_c, shift_c=shift_c, bits=bits,
        ),
        grid=(m + 1, nc),
        in_specs=[
            # Hop i's chunk c; the tail step re-uses the last block, so
            # nothing is fetched twice.
            pl.BlockSpec(
                (1, chunk, r),
                lambda i, c: (
                    jnp.minimum(i, m - 1), jnp.where(i < m, c, nc - 1), 0
                ),
            ),
            pl.BlockSpec((nc, chunk, r), resident),
            pl.BlockSpec((m, 1, r), resident),
        ],
        out_specs=pl.BlockSpec((nc, chunk, r), resident),
        out_shape=jax.ShapeDtypeStruct((nc, chunk, r), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, nc, chunk, r), vs.dtype),  # hop slots (wire)
            pltpu.VMEM((nc, chunk, r), jnp.float32),  # resident running V̄
            pltpu.VMEM((r, r), jnp.float32),          # Gram accumulator
            pltpu.VMEM((r, r), jnp.float32),          # polar factor Z
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_mosaic_vmem_limit(
                m, dp, chunk, r, jnp.dtype(vs.dtype).itemsize
            ),
        ),
        interpret=interpret,
    )(
        vs,
        ref.astype(jnp.float32).reshape(nc, chunk, r),
        scales.astype(jnp.float32).reshape(m, 1, r),
    )
    return out.reshape(dp, r)[:d]
