"""Overlapped ring schedule: consume each neighbor's basis as it arrives.

The gather topology pays m·d·r up front and materializes the (m, d, r)
stack before any round compute starts.  The ring topology interleaves the
two instead: every shard's (d, r) basis circulates a ``ppermute`` ring in
d-chunks, and on each of the m-1 hops the receiving shard immediately runs
that basis's share of the round — Gram against the reference
(``Vⱼᵀ·ref``, accumulated chunk by chunk as the chunks land), the r x r
polar, and the aligned accumulation into the running V̄.  Two consequences:

  * **Overlap.**  The hop h+1 ``ppermute`` of a chunk depends only on the
    hop-h *transfer*, not on the hop-h *compute*, and within a hop chunk
    c+1's transfer is independent of chunk c's Gram matmul — so XLA's
    async collective-permute (start/done pairs under the latency-hiding
    scheduler) runs the wire and the MXU concurrently.  The chunk size is
    the overlap granularity: smaller chunks pipeline tighter at more
    per-transfer latency.
  * **O(d·r) working set.**  A shard ever holds three (d, r) buffers — the
    circulating basis, the reference, and the running average — so the
    (m, d, r) stack is *never materialized*.  This is the memory story for
    large m: the gather topology's stack is m times bigger than the answer.

The per-hop compute is deliberately plain ``jnp`` (chunked tall-skinny
matmuls + the ``polar=`` method of ``repro.core.procrustes``): there is no
stacked (m, d, r) operand for the Pallas streaming kernels to win on, and
(chunk, r) GEMMs are already MXU-native, so ``backend=`` affects only the
stages outside the ring (e.g. the shard-local covariance).  With
``polar="newton-schulz"`` the whole hop is matmul-only; ``polar="svd"``
round-trips an r x r SVD per hop (latency-bound — prefer Newton–Schulz on
TPU).

Numerics: each shard accumulates the m contributions in its own ring
order, so unlike the psum topology the result is shard-replicated only up
to f32 summation-order rounding (~1e-7); the parity suite asserts ≤ 1e-5
f64 subspace distance against the serial oracle.  Core imports are
function-level: this module sits below ``repro.core`` in the layering
(see ``repro.comm``).

Compile-cost trade: the m-1 hops are *unrolled* Python loops, so program
size and trace/compile time grow O(n_iter · m).  Deliberate — the overlap
above needs the scheduler to see across hops, and a ``fori_loop`` body
would wall each transfer off from the previous hop's compute (XLA does
not software-pipeline collectives across while iterations).  The unroll
is cheap through the hundreds-of-shards range that the cost table covers;
for meshes far beyond that, or under ``polar="svd"`` (an r x r SVD *per
hop*), expect compile time to dominate and prefer the gather topology.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.comm.membership import Membership, resolve_membership
from repro.comm.quantize import (
    from_wire,
    get_codec,
    shard_key,
    to_wire,
    wire_broadcast,
)

__all__ = [
    "DEFAULT_RING_CHUNK",
    "chunk_spans",
    "ring_rounds",
    "fused_ring_rounds",
]

# Salt for the ring's per-shard stochastic-rounding streams ("RING").
_RING_SALT = 0x52494E47

# Rows per circulating chunk — the overlap granularity.  Matches the
# Pallas kernels' default d-block (bk=2048): ~2048*r*4 bytes per transfer
# keeps per-hop latency amortized while still splitting large-d bases into
# several in-flight transfers.  This is the legacy fixed default; the
# planner (``repro.plan.choose_ring_chunk``) sizes the chunk from the
# device's latency-bandwidth product instead (the d·r-vs-per-hop-latency
# rule, DESIGN.md §8) and threads it through ``plan="auto"``.
DEFAULT_RING_CHUNK = 2048


def chunk_spans(d: int, chunk: int) -> List[Tuple[int, int]]:
    """[start, end) row spans tiling d; the last span may be short.

    This is the single home of the ring's chunk geometry: the jnp schedule
    below, the fused Pallas kernel
    (``repro.kernels.procrustes_align.fused_ring_round``) and the planner's
    sizing rule (``repro.plan.choose_ring_chunk``) all derive their span
    count from here, so the kernel cannot drift from the wire schedule it
    fuses.  Pure arithmetic — safe to import from the cost model.
    """
    chunk = max(1, min(chunk, d))
    return [(s, min(s + chunk, d)) for s in range(0, d, chunk)]


# Back-compat alias (pre-export spelling).
_chunk_spans = chunk_spans


def _aligned_contribution(chunks, ref_chunks, *, polar: str):
    """align(V, ref) for a chunked (d, r) basis: chunk-accumulated Gram,
    one r x r polar, chunked apply.  All f32.

    This is Algorithm 1's alignment step (eq. (5)/(6)) evaluated
    incrementally: the Gram ``Vᵀ ref`` accumulates as the chunks land,
    so the rotation is available one polar solve after the last chunk
    arrives.  Each hop's aligned output feeds Algorithm 1's averaging
    step via the running accumulator in ``_ring_round`` — the ring never
    needs the (m, d, r) stack the stacked form averages over.  A hop
    moves d·r words, matching the paper's §2.1 / Remark 2 accounting
    (``repro.comm.comm_cost``'s ring row)."""
    from repro.core.procrustes import polar_factor

    g = sum(c.T @ rc for c, rc in zip(chunks, ref_chunks))
    z = polar_factor(g, polar=polar)
    return [c @ z for c in chunks]


def ring_rounds(
    v_local: jax.Array,
    ref: jax.Array | None = None,
    *,
    axis_name: str,
    n_iter: int = 1,
    polar: str = "svd",
    orth: str = "qr",
    chunk: int = DEFAULT_RING_CHUNK,
    comm_bits: int = 32,
    membership: Membership | None = None,
) -> jax.Array:
    """``n_iter`` Algorithm-1 rounds over a mesh axis via the ring schedule.

    Args:
      v_local: (d, r) local basis on each shard of ``axis_name``.
      ref: optional (d, r) reference; defaults to shard 0's basis via one
        wire-precision broadcast (the paper's choice) — the first
        *surviving* shard's under a degraded ``membership``.
      n_iter: refinement rounds; each costs (m-1) hop messages of
        ``quantize.message_bits(d, r, comm_bits)`` bits ((m'-1) under a
        degraded membership).
      polar / orth: round methods, as everywhere (validated up front).
      chunk: rows per circulating chunk; need not divide d.
      comm_bits: wire precision of the circulating chunks (32/16/8, see
        ``repro.comm.quantize``).  Lossy tiers quantize *once* per round
        and circulate the wire payload verbatim — receivers decode for
        compute but forward the original chunks, so hop count adds no
        re-quantization error — with the per-round encoding residual
        carried as error-feedback state into the next round's send.
      membership: jit-static active-shard mask (``repro.comm.Membership``).
        The ring's permutation is built over the survivors only, so dead
        hops are *not traced* — a degraded round is m'-1 hops linking the
        survivors in mesh order, exactly the ring a fresh m'-shard job
        would run, still O(d·r) working set.  The error-feedback residual
        is per-call state: rounds inside one call share one membership, so
        telescoping is preserved; a membership *change* starts a new call
        with a fresh (zero) residual — the stale residual describes a
        quantization debt owed to a mesh that no longer exists
        (``repro.runtime.elastic`` groups rounds accordingly).  After the
        rounds, one exact f32 broadcast from the first survivor hands the
        result to the dead shards too (their ring buffers held zeros), so
        the output is replicated mesh-wide — the basis a recovering shard
        Procrustes-aligns to when it rejoins.

    Returns the (d, r) round output in ``v_local.dtype`` (replicated up to
    the summation-order rounding discussed in the module docstring; lossy
    tiers are replicated exactly as far, since every shard decodes the
    same m payloads).
    """
    from repro.comm.topology import broadcast_from
    from repro.core.orthonorm import orthonormalize, resolve_orth
    from repro.core.procrustes import resolve_polar

    resolve_polar(polar)
    resolve_orth(orth)
    codec = get_codec(comm_bits)
    m = jax.lax.axis_size(axis_name)
    mem = resolve_membership(membership, m)
    base_key = shard_key(axis_name, _RING_SALT) if codec.stochastic else None
    if ref is None:
        bkey = (
            jax.random.fold_in(base_key, 0) if codec.stochastic else None
        )
        ref = wire_broadcast(
            v_local, axis_name, codec, src=mem.first_active, key=bkey
        )
    out = ref
    err = jnp.zeros(v_local.shape, jnp.float32) if codec.lossy else None
    for k in range(max(n_iter, 1)):
        rkey = (
            jax.random.fold_in(base_key, k + 1) if codec.stochastic else None
        )
        vbar, err = _ring_round(
            v_local, out, axis_name=axis_name, membership=mem, polar=polar,
            chunk=chunk, codec=codec, err=err, key=rkey,
        )
        out = orthonormalize(vbar, orth=orth).astype(v_local.dtype)
    if not mem.is_full:
        # Dead shards were never ppermute targets, so their buffers (and
        # hence their `out`) are garbage; replicate the survivors' answer
        # mesh-wide from the first survivor (one exact f32 d·r all-reduce,
        # priced by the cost model's degraded-ring sync term).
        out = broadcast_from(out, axis_name, src=mem.first_active)
    return out


def fused_ring_rounds(
    v_local: jax.Array,
    ref: jax.Array | None = None,
    *,
    axis_name: str,
    n_iter: int = 1,
    chunk: int = DEFAULT_RING_CHUNK,
    comm_bits: int = 32,
    membership: Membership | None = None,
) -> jax.Array:
    """``n_iter`` rounds with the hop schedule fused *into* the kernel.

    This is the ``("pallas", "ring")`` execution cell (DESIGN.md §3.3): the
    wire still moves exactly the ring's per-round payload — each shard's
    (d, r) basis at wire precision, m'-1 hop-equivalents on the wire (the
    all-gather below lowers to a ring of m'-1 hops) — but the per-hop
    Gram / Newton–Schulz polar / accumulate runs *inside* one Pallas launch
    per round (``repro.kernels.ops.fused_ring_round``), with each hop's
    basis chunked into double-buffered VMEM scratch while the previous
    hop's compute occupies the MXU.  The cell pins ``polar="newton-schulz"``
    and ``orth="cholesky-qr2"`` (the matmul-only methods the kernel fuses);
    ``repro.core.distributed`` routes every other (polar, orth) pair to the
    jnp schedule above.

    Collective structure (the jaxpr the structure tests assert): the
    error-feedback recurrence depends only on ``v_local`` and the previous
    round's residual — never on a round *output* — so all ``n_iter``
    encodes and wire all-gathers are hoisted ahead of the first launch.
    The program is [ref broadcast, n_iter encode+gather, n_iter
    pallas_calls] with **zero collectives and zero XLA compute between
    launches**: round k's (d, r) f32 output feeds round k+1's reference
    operand directly.  At 32 bits the payload is round-invariant, so a
    single all-gather feeds every launch.

    ``comm_bits`` follows the jnp ring's contract exactly — quantize once
    per round, per-shard error feedback, same salt and per-round key folds
    (``_RING_SALT``) — so the wire payloads are bit-identical to the jnp
    schedule's and the ``PARITY_TOL[bits]`` bounds carry over.  Under a
    degraded ``membership`` the survivors' rows are selected by static
    indexing (row 0 = first survivor, the reference default), every shard
    — dead ones included — decodes the same m' payloads, and the output is
    replicated mesh-wide with *no* post-round resync broadcast (unlike the
    jnp ring, dead shards here hold the gathered payloads too).

    Returns the (d, r) round output in ``v_local.dtype``.
    """
    from repro.kernels import ops as kops

    codec = get_codec(comm_bits)
    m = jax.lax.axis_size(axis_name)
    mem = resolve_membership(membership, m)
    base_key = shard_key(axis_name, _RING_SALT) if codec.stochastic else None
    if ref is None:
        bkey = (
            jax.random.fold_in(base_key, 0) if codec.stochastic else None
        )
        ref = wire_broadcast(
            v_local, axis_name, codec, src=mem.first_active, key=bkey
        )
    idxs = None if mem.is_full else jnp.asarray(mem.indices)

    # Stage every round's wire payload BEFORE the first launch (see
    # docstring): the EF recurrence never reads a round output.
    payloads = []
    if codec.lossy:
        err = jnp.zeros(v_local.shape, jnp.float32)
        for k in range(max(n_iter, 1)):
            rkey = (
                jax.random.fold_in(base_key, k + 1)
                if codec.stochastic else None
            )
            send = v_local.astype(jnp.float32) + err
            data, scale = codec.encode(send, key=rkey)
            err = codec.residual(send, data, scale)
            g = from_wire(jax.lax.all_gather(to_wire(data), axis_name), codec)
            gs = (
                jax.lax.all_gather(scale, axis_name)
                if scale is not None else None
            )
            if idxs is not None:
                g = g[idxs]
                gs = None if gs is None else gs[idxs]
            payloads.append((g, gs))
    else:
        g = jax.lax.all_gather(v_local.astype(jnp.float32), axis_name)
        if idxs is not None:
            g = g[idxs]
        payloads = [(g, None)] * max(n_iter, 1)

    out = ref.astype(jnp.float32)
    for g, gs in payloads:
        out = kops.fused_ring_round(
            g, out, scales=gs, ring_chunk=chunk, use_kernel=True
        )
    return out.astype(v_local.dtype)


def _ring_round(
    v_local: jax.Array,
    ref: jax.Array,
    *,
    axis_name: str,
    membership: Membership,
    polar: str,
    chunk: int,
    codec,
    err,
    key,
):
    """One round: circulate the bases m'-1 hops, aligning each arrival.

    Returns ``(vbar, err)`` — the pre-orthonormalization average and the
    updated error-feedback residual (``None`` at 32 bits).  The circulating
    chunk scratch is held in the codec's **wire dtype** (s8 / bf16 / f32):
    a bf16 hop forwards bf16, never a silently-upcast f32 copy, and the
    int8 tier ppermutes its f32[r] column scale alongside the payload as
    one extra small transfer per hop (the 32·r term in the cost model).

    The permutation links the *survivors* in mesh order — at full
    membership exactly the classic ``(i, (i+1) % m)`` ring.  Dead shards
    appear in no (src, dst) pair, so they neither send nor receive
    (``ppermute`` leaves non-targets holding zeros); their local compute
    runs on those zeros and is discarded by the post-round sync in
    ``ring_rounds``.
    """
    d = v_local.shape[0]
    spans = chunk_spans(d, chunk)
    ref_c = [ref[s:e].astype(jnp.float32) for s, e in spans]
    idxs = membership.indices
    k = membership.m_active
    perm = [(idxs[i], idxs[(i + 1) % k]) for i in range(k)]

    if codec.lossy:
        send = v_local.astype(jnp.float32) + err
        data, scale = codec.encode(send, key=key)
        err = codec.residual(send, data, scale)
        buf_c = [to_wire(data[s:e]) for s, e in spans]
    else:
        scale = None
        # The exact-wire payload is the same every round, and XLA would
        # merge later rounds' hops into the first round's (CSE), holding
        # all m'-1 received bases alive across rounds instead of the
        # O(d·r) working set above.  Adding 0·ref[0, 0] (exact for a
        # finite reference; XLA does not fold a float product with zero)
        # makes each round's payload a value of its own.
        tie = 0.0 * ref[:1, :1].astype(jnp.float32)
        buf_c = [v_local[s:e].astype(jnp.float32) + tie for s, e in spans]

    def dec(chunks, sc):
        if not codec.lossy:
            return chunks
        return [codec.decode(from_wire(c, codec), sc) for c in chunks]

    # Own basis: consume the *decoded* payload, so all m' shards average the
    # identical m' wire-precision bases (replication is preserved).
    acc_c = _aligned_contribution(dec(buf_c, scale), ref_c, polar=polar)
    for _ in range(k - 1):
        # Receive the left neighbor's basis chunk by chunk; the Gram of
        # chunk c can start as soon as chunk c lands, overlapping the
        # remaining transfers (and the next hop overlaps this hop's apply).
        buf_c = [jax.lax.ppermute(c, axis_name, perm) for c in buf_c]
        if scale is not None:
            scale = jax.lax.ppermute(scale, axis_name, perm)
        contrib = _aligned_contribution(dec(buf_c, scale), ref_c, polar=polar)
        acc_c = [a + c for a, c in zip(acc_c, contrib)]
    vbar = acc_c[0] if len(acc_c) == 1 else jnp.concatenate(acc_c, axis=0)
    return vbar / k, err
