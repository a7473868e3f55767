"""Hierarchical two-level aggregation over a (pod, local) mesh.

Every flat topology prices each hop at one bandwidth, but a multi-pod
mesh has a fast intra-pod link (ICI) and a slow inter-pod fabric (DCN).
``hier_rounds`` is the divide-and-conquer schedule for that shape (the
Fan–Wang–Wang–Zhu aggregation, arXiv 1702.06488, mapped onto two mesh
levels): each refinement round

  1. **aligns locally** — the same align-then-average body the psum
     topology runs (Procrustes to the shared reference, backend-routed
     through the Pallas kernels when ``backend="pallas"``);
  2. **reduces intra-pod** — one masked f32 psum over the ``local``
     axis, so every local slot of pod q holds the pod's summed aligned
     contribution (the pod-representative V̄_q, un-normalized).  Dead
     shards contribute exact zeros, exactly as in the flat psum arm;
  3. **rings inter-pod** — only the p pod sums circulate a chunked
     ppermute ring over the ``pod`` axis (``repro.comm.ring``'s hop
     idiom: wire-dtype chunk buffers, the int8 f32[r] scale ppermuted
     alongside), so the slow link carries n·(p'-1) messages per device
     instead of the flat ring's n·(m'-1).  The contributions are
     *already aligned* to the shared reference, so hops accumulate —
     no per-hop Procrustes — and the round's mean over the m' global
     survivors is exact up to summation order;
  4. **orthonormalizes** the global mean into the next reference.

Quantize-the-slow-link rule: ``comm_bits`` applies to the inter-pod
wire only (the ring hops and the reference's pod-level broadcast stage);
the intra-pod psum always runs exact f32 — the fast link is not the
bottleneck, and keeping it exact means the per-pod sums entering the
codec are identical across a pod's local slots (so one error-feedback
residual per pod, replicated, not one per shard).

Membership masks per level (``repro.comm.membership.pod_membership``):
a dead shard inside a live pod is masked out of the local psum (and the
mean reweights to the m' global survivors); a fully dead pod drops out
of the inter-pod ring permutation (its hops are not traced), and one
exact f32 broadcast back down from the first surviving pod re-replicates
the answer on its devices after the rounds.

Layering: like ``repro.comm.ring``, core/kernels imports are
function-level, so this module stays below ``repro.core``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.comm.membership import (
    Membership,
    pod_membership,
    resolve_membership,
)
from repro.comm.quantize import (
    from_wire,
    get_codec,
    shard_key,
    to_wire,
    wire_broadcast,
)
from repro.comm.ring import DEFAULT_RING_CHUNK, chunk_spans
from repro.comm.topology import DATA_AXIS, POD_AXIS, broadcast_from

__all__ = ["hier_rounds"]

# Salt for the inter-pod stochastic-rounding streams ("HIER").  Keyed by
# *pod* index (not shard): every local slot of a pod encodes the same pod
# sum and must draw the same rounding, or the ring's replication breaks.
_HIER_SALT = 0x48494552


def _align_local(v, ref, *, backend: str, polar: str):
    """One shard's Procrustes align, backend-routed (the psum arm's body)."""
    if backend == "pallas":
        from repro.kernels import ops as kops

        return kops.align_one(v, ref, polar=polar, use_kernel=True)
    from repro.core import procrustes

    return procrustes.align(v, ref, polar=polar)


def _ring_psum(
    x: jax.Array,
    *,
    axis_name: str,
    pod_mem: Membership,
    chunk: int,
    codec,
    err,
    key,
):
    """Sum ``x`` over the active pods of ``axis_name`` via a chunked
    ppermute ring at wire precision; returns ``(total, err)``.

    The hop loop is ``repro.comm.ring._ring_round``'s wire idiom minus
    the per-hop Procrustes: payloads are quantized once (error feedback
    carried in ``err``), circulate in wire dtype, and every pod decodes
    the same p' payloads — so the accumulated total is replicated across
    pods up to f32 summation order.  Dead pods appear in no (src, dst)
    pair: they neither send nor receive, and their devices' total is
    garbage until ``hier_rounds``'s post-round resync broadcast.
    """
    d = x.shape[0]
    spans = chunk_spans(d, chunk)
    idxs = pod_mem.indices
    k = pod_mem.m_active
    perm = [(idxs[i], idxs[(i + 1) % k]) for i in range(k)]

    if codec.lossy:
        send = x.astype(jnp.float32) + err
        data, scale = codec.encode(send, key=key)
        err = codec.residual(send, data, scale)
        buf_c = [to_wire(data[s:e]) for s, e in spans]
    else:
        scale = None
        buf_c = [x[s:e].astype(jnp.float32) for s, e in spans]

    def dec(chunks, sc):
        if not codec.lossy:
            return chunks
        return [codec.decode(from_wire(c, codec), sc) for c in chunks]

    # Own pod sum: consume the decoded payload, so all pods average the
    # identical p' wire-precision contributions.
    acc_c = dec(buf_c, scale)
    for _ in range(k - 1):
        buf_c = [jax.lax.ppermute(c, axis_name, perm) for c in buf_c]
        if scale is not None:
            scale = jax.lax.ppermute(scale, axis_name, perm)
        acc_c = [a + c for a, c in zip(acc_c, dec(buf_c, scale))]
    total = acc_c[0] if len(acc_c) == 1 else jnp.concatenate(acc_c, axis=0)
    return total, err


def hier_rounds(
    v_local: jax.Array,
    ref: jax.Array | None = None,
    *,
    pod_axis: str = POD_AXIS,
    local_axis: str = DATA_AXIS,
    n_iter: int = 1,
    backend: str = "xla",
    polar: str = "svd",
    orth: str = "qr",
    chunk: int = DEFAULT_RING_CHUNK,
    comm_bits: int = 32,
    membership: Membership | None = None,
) -> jax.Array:
    """``n_iter`` Algorithm-1 rounds over a 2-D (pod, local) mesh.

    Args:
      v_local: (d, r) local basis on each (pod, local) shard.
      pod_axis / local_axis: the two mesh axis names (the slow and fast
        link respectively); defaults are the repo-wide constants.
      ref: optional (d, r) reference; defaults to the first surviving
        shard's basis via a two-stage broadcast — exact f32 up the
        ``local`` axis, then wire-precision across the ``pod`` axis.
      n_iter: refinement rounds; each costs one intra-pod f32 psum plus
        (p'-1) inter-pod hop messages of
        ``quantize.message_bits(d, r, comm_bits)`` bits per device.
      backend / polar / orth: compute knobs, as everywhere (the local
        align is the psum arm's backend-routed body).
      chunk: rows per circulating chunk of the inter-pod ring (the
        planner sizes this against the *DCN* latency-bandwidth product).
      comm_bits: wire precision of the inter-pod payloads only — the
        quantize-the-slow-link rule; intra-pod collectives are exact.
      membership: jit-static active-shard mask over the *flattened*
        pod-major axis (shard q·local + l = pod q, slot l).  See the
        module docstring for the per-level masking contract.

    Returns the (d, r) round output in ``v_local.dtype``, replicated
    mesh-wide (dead pods included, via the resync broadcast).
    """
    from repro.core.orthonorm import orthonormalize, resolve_orth
    from repro.core.procrustes import resolve_polar

    resolve_polar(polar)
    resolve_orth(orth)
    codec = get_codec(comm_bits)
    p = jax.lax.axis_size(pod_axis)
    local = jax.lax.axis_size(local_axis)
    mem = resolve_membership(membership, p * local)
    pmem = pod_membership(mem, p)
    base_key = (
        shard_key(pod_axis, _HIER_SALT) if codec.stochastic else None
    )
    src_pod, src_loc = divmod(mem.first_active, local)
    if ref is None:
        # Two-stage broadcast of the first survivor's basis: up the fast
        # axis exact, across the slow axis at wire precision.  Stage one
        # hands every pod its slot-src_loc basis; stage two's mask keeps
        # only the source pod's, so the intermediate garbage of pods
        # whose slot src_loc is dead never survives.
        ref = (
            broadcast_from(v_local, local_axis, src=src_loc)
            if local > 1 else v_local
        )
        if p > 1:
            bkey = (
                jax.random.fold_in(base_key, 0) if codec.stochastic else None
            )
            ref = wire_broadcast(
                ref, pod_axis, codec, src=src_pod, key=bkey
            ).astype(v_local.dtype)
    alive = None
    if not mem.is_full:
        # Traced per-shard gate from the static mask, indexed by the
        # flattened pod-major position of this device.
        flat = (
            jax.lax.axis_index(pod_axis) * local
            + jax.lax.axis_index(local_axis)
        )
        alive = jnp.asarray(mem.active)[flat]
    err = (
        jnp.zeros(v_local.shape, jnp.float32)
        if (codec.lossy and p > 1) else None
    )
    out = ref
    for k in range(max(n_iter, 1)):
        aligned = _align_local(v_local, out, backend=backend, polar=polar)
        contrib = aligned.astype(jnp.float32)
        if alive is not None:
            contrib = jnp.where(alive, contrib, jnp.zeros_like(contrib))
        pod_sum = (
            jax.lax.psum(contrib, local_axis) if local > 1 else contrib
        )
        if p > 1:
            rkey = (
                jax.random.fold_in(base_key, k + 1)
                if codec.stochastic else None
            )
            total, err = _ring_psum(
                pod_sum, axis_name=pod_axis, pod_mem=pmem, chunk=chunk,
                codec=codec, err=err, key=rkey,
            )
        else:
            total = pod_sum
        vbar = (total / mem.m_active).astype(v_local.dtype)
        out = orthonormalize(vbar, orth=orth).astype(v_local.dtype)
    if p > 1 and not pmem.is_full:
        # Dead pods were never ppermute targets; broadcast the answer
        # back down from the first surviving pod (one exact f32 d·r
        # all-reduce over the pod axis — the cost model's sync term).
        out = broadcast_from(out, pod_axis, src=pmem.first_active)
    return out
