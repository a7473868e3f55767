"""Acceptance suite for the fused *ring-scheduled* one-launch round.

The tentpole claims of the ``backend="pallas", topology="ring",
polar="newton-schulz", orth="cholesky-qr2"`` cell (DESIGN.md §3.3):

  * The ring-round kernel (``kernels.procrustes_align.fused_ring_round``)
    matches its XLA oracle (``kernels.ref.fused_ring_round``) on ragged
    shapes — including ``ring_chunk`` not dividing d and d < chunk (the
    stack is zero-padded to whole chunks) — and on every wire dtype
    (f32 / bf16 / int8 + scales).
  * ``n_iter`` rounds of ``repro.comm.ring.fused_ring_rounds`` lower to
    exactly ``n_iter`` pallas_calls with **zero XLA collectives and zero
    XLA compute between launches**: the wire is staged up front (error
    feedback depends only on the local basis, so every round's gather
    hoists before the first launch) and each launch's f32 output feeds
    the next launch's reference directly.
  * The full collective (``procrustes_average_collective`` on the cell)
    matches the serial oracle to ``PARITY_TOL[bits]`` f64 subspace
    distance over comm_bits in {32, 16, 8}, with outputs exactly
    replicated across shards, and a degraded ring (dead shard) matches
    the fresh survivor-count oracle.

These lanes run the kernel interpreted; ``tests/test_tpu_compile.py``
compiles it for a described v5e.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import jaxpr_primitives, run_with_devices, subspace_dist64

from repro.comm.quantize import PARITY_TOL, get_codec
from repro.comm.ring import DEFAULT_RING_CHUNK, chunk_spans, fused_ring_rounds
from repro.kernels import procrustes_align, ref

# Primitives that must never appear in the fused path's jaxpr ("qr" is a
# primitive name, not a substring — "sqrt" would false-alarm) plus the
# collectives that must never appear *between* launches.
BANNED = {"svd", "qr", "geqrf", "householder_product"}
COLLECTIVES = {"psum", "all_gather", "ppermute", "all_to_all", "pmax", "pmin"}


def _stack(seed, m, d, r):
    key = jax.random.PRNGKey(seed)
    return jnp.linalg.qr(jax.random.normal(key, (m, d, r)))[0]


# ---------------------------------------------------------------------------
# Kernel vs oracle (single device, interpret mode).


@pytest.mark.parametrize(
    "m,d,r,chunk",
    [
        (4, 96, 8, 40),     # chunk does not divide d (zero-padded tail)
        (3, 33, 5, 8),      # ragged everything
        (1, 7, 3, 16),      # d < chunk (one chunk of d rows), m == 1
        (8, 128, 16, 128),  # chunk == d (one chunk per hop)
        (2, 100, 4, 33),    # a one-row last chunk
    ],
)
def test_fused_ring_kernel_matches_oracle(m, d, r, chunk):
    """Kernel == oracle to f32 roundoff on ragged shapes; the zero rows
    that pad d to whole chunks contribute exact zeros."""
    vs = _stack(m * d + r, m, d, r)
    zk = procrustes_align.fused_ring_round(
        vs, vs[0], ring_chunk=chunk, interpret=True
    )
    zo = ref.fused_ring_round(vs, vs[0])
    np.testing.assert_allclose(np.asarray(zk), np.asarray(zo), atol=1e-6)
    np.testing.assert_allclose(np.asarray(zk.T @ zk), np.eye(r), atol=1e-5)


def test_fused_ring_kernel_wire_dtypes():
    """The kernel consumes the wire stack at wire width: bf16 upcasts and
    int8 applies its per-column scales in-register, matching the decoding
    oracle."""
    m, d, r = 4, 96, 8
    vs = _stack(1, m, d, r)
    # bf16 wire
    vb = vs.astype(jnp.bfloat16)
    zk = procrustes_align.fused_ring_round(
        vb, vs[0], ring_chunk=40, interpret=True
    )
    zo = ref.fused_ring_round(vb, vs[0])
    np.testing.assert_allclose(np.asarray(zk), np.asarray(zo), atol=1e-5)
    # int8 wire + scales (encode with the registry codec so the stack is a
    # genuine wire payload, not an arbitrary s8 tensor)
    codec = get_codec(8)
    key = jax.random.PRNGKey(3)
    data, scale = jax.vmap(
        lambda v, k: codec.encode(v, key=k)
    )(vs, jax.random.split(key, m))
    zk8 = procrustes_align.fused_ring_round(
        data, vs[0], scales=scale, ring_chunk=40, interpret=True
    )
    zo8 = ref.fused_ring_round(data, vs[0], scale)
    np.testing.assert_allclose(np.asarray(zk8), np.asarray(zo8), atol=1e-5)


def test_fused_ring_kernel_scale_validation():
    """Scales are required exactly for the int8 wire: both mismatches are
    loud errors, as is an unknown wire dtype."""
    m, d, r = 2, 32, 4
    vs = _stack(2, m, d, r)
    with pytest.raises(ValueError):
        procrustes_align.fused_ring_round(
            vs, vs[0], scales=jnp.ones((m, r)), interpret=True
        )
    with pytest.raises(ValueError):
        procrustes_align.fused_ring_round(
            vs.astype(jnp.int8), vs[0], interpret=True
        )
    with pytest.raises(ValueError):
        procrustes_align.fused_ring_round(
            vs.astype(jnp.float16), vs[0], interpret=True
        )


@pytest.mark.parametrize("dtype,fits", [
    (jnp.float32, False),   # (3*4 + 12) * 8192 * 128 B = 24 MiB
    (jnp.int8, True),       # (3*1 + 12) * 8192 * 128 B = 15 MiB
])
def test_compiled_fused_ring_refuses_over_the_vmem_envelope(dtype, fits):
    """Compiled, a working set over the planner's VMEM envelope raises a
    ValueError naming it while tracing, before Mosaic sees the kernel; the
    planner marks exactly that cell infeasible."""
    from repro.plan.planner import score_cells

    m, d, r = 4, 8192, 128
    args = [jax.ShapeDtypeStruct((m, d, r), dtype),
            jax.ShapeDtypeStruct((d, r), jnp.float32)]
    if dtype == jnp.int8:
        args.append(jax.ShapeDtypeStruct((m, r), jnp.float32))
    trace = lambda: jax.eval_shape(
        lambda *a: procrustes_align.fused_ring_round(*a, interpret=False),
        *args,
    )
    bits = 32 if dtype == jnp.float32 else 8
    cell = score_cells(
        m=m, d=d, r=r, device_kind="tpu", backend="pallas", topology="ring",
        polar="newton-schulz", orth="cholesky-qr2", comm_bits=bits,
    )[0]
    assert cell.feasible == fits
    if fits:
        assert trace().shape == (d, r)
    else:
        with pytest.raises(ValueError, match="VMEM envelope"):
            trace()


def test_chunk_spans_single_home():
    """Satellite: the ring chunking vocabulary has one home — the kernel,
    the jnp ring, and the planner all price the same span count."""
    assert chunk_spans(100, 33) == [(0, 33), (33, 66), (66, 99), (99, 100)]
    assert chunk_spans(7, 16) == [(0, 7)]
    assert DEFAULT_RING_CHUNK >= 1
    nc = len(chunk_spans(100, 33))
    from repro.plan.planner import score_cells

    cell = score_cells(
        m=2, d=100, r=4, device_kind="cpu", backend="pallas",
        topology="ring", polar="newton-schulz", orth="cholesky-qr2",
        ring_chunk=33,
    )[0]
    assert cell.ring_chunk == 33 and nc == 4


# ---------------------------------------------------------------------------
# Launch structure: n_iter pallas_calls, nothing on the wire in between.


@pytest.mark.parametrize("n_iter", [1, 3])
def test_jaxpr_one_launch_per_round_zero_collectives_between(n_iter):
    """Acceptance: ``n_iter`` rounds are exactly ``n_iter`` pallas_calls;
    every collective (ref broadcast + staged wire gather) hoists before
    the first launch; no SVD / Householder QR / LAPACK anywhere."""
    m = 4
    vs = _stack(0, m, 64, 4)[0]

    def f(v):
        return fused_ring_rounds(v, axis_name="mach", n_iter=n_iter, chunk=16)

    prims = jaxpr_primitives(
        jax.make_jaxpr(f, axis_env=[("mach", m)])(vs)
    )
    assert prims.count("pallas_call") == n_iter
    assert not BANNED.intersection(prims), sorted(BANNED.intersection(prims))
    assert "cholesky" not in prims and "triangular_solve" not in prims
    first = prims.index("pallas_call")
    last = len(prims) - 1 - prims[::-1].index("pallas_call")
    between = set(prims[first + 1 : last])
    assert not COLLECTIVES.intersection(between), sorted(
        COLLECTIVES.intersection(between)
    )
    # All collectives sit strictly before the first launch.
    assert not COLLECTIVES.intersection(prims[first:]), sorted(
        COLLECTIVES.intersection(prims[first:])
    )


def test_jaxpr_quantized_wire_still_hoists(n_iter=3):
    """Error feedback depends only on the local basis, so even the lossy
    tiers stage every round's gather before the first launch."""
    m = 4
    vs = _stack(5, m, 64, 4)[0]
    for bits in (16, 8):
        def f(v):
            return fused_ring_rounds(
                v, axis_name="mach", n_iter=n_iter, chunk=16, comm_bits=bits
            )

        prims = jaxpr_primitives(jax.make_jaxpr(f, axis_env=[("mach", m)])(vs))
        assert prims.count("pallas_call") == n_iter
        first = prims.index("pallas_call")
        assert not COLLECTIVES.intersection(prims[first:])


# ---------------------------------------------------------------------------
# Multi-device parity cube (subprocess with 8 fake CPU devices).


def test_fused_ring_collective_parity_cube():
    """The full cell through ``procrustes_average_collective``: parity vs
    the serial oracle <= PARITY_TOL[bits] over comm_bits in {32, 16, 8},
    outputs exactly replicated across shards."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from repro.core.distributed import procrustes_average_collective
        from repro.core import procrustes_fix_average
        from repro.core.metrics import subspace_dist64

        m, d, r = 8, 96, 8
        vs = jnp.linalg.qr(
            jax.random.normal(jax.random.PRNGKey(0), (m, d, r))
        )[0]
        oracle = procrustes_fix_average(
            vs, polar="newton-schulz", orth="cholesky-qr2"
        )

        def run(bits):
            f = jax.pmap(
                lambda v: procrustes_average_collective(
                    v, axis_name="mach", topology="ring", backend="pallas",
                    polar="newton-schulz", orth="cholesky-qr2",
                    ring_chunk=32, comm_bits=bits,
                ),
                axis_name="mach",
            )
            return f(vs)

        for bits in (32, 16, 8):
            got = run(bits)
            rep = float(jnp.max(jnp.abs(got - got[0])))
            dist = subspace_dist64(oracle, got[0])
            print(f"bits={bits} dist={dist:.3e} rep={rep}")
        """,
        n_devices=8,
    )
    for line in out.strip().splitlines():
        fields = dict(kv.split("=") for kv in line.split())
        bits = int(fields["bits"])
        assert float(fields["dist"]) <= PARITY_TOL[bits], line
        assert float(fields["rep"]) == 0.0, line


def test_fused_ring_collective_degraded_membership():
    """A dead shard shrinks the ring to m'-1 staged hops: survivors match
    the fresh-m' oracle and stay exactly replicated (the dead shard's
    output is unconstrained)."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from repro.comm.membership import Membership
        from repro.core.distributed import procrustes_average_collective
        from repro.core import procrustes_fix_average
        from repro.core.metrics import subspace_dist64

        m, d, r = 8, 96, 8
        dead = 2
        mem = Membership.from_dead(m, [dead])
        vs = jnp.linalg.qr(
            jax.random.normal(jax.random.PRNGKey(1), (m, d, r))
        )[0]
        alive = [i for i in range(m) if i != dead]
        oracle = procrustes_fix_average(
            vs[jnp.asarray(alive)], polar="newton-schulz", orth="cholesky-qr2"
        )
        got = jax.pmap(
            lambda v: procrustes_average_collective(
                v, axis_name="mach", topology="ring", backend="pallas",
                polar="newton-schulz", orth="cholesky-qr2",
                ring_chunk=32, membership=mem,
            ),
            axis_name="mach",
        )(vs)
        ga = got[jnp.asarray(alive)]
        dist = subspace_dist64(oracle, ga[0])
        rep = float(jnp.max(jnp.abs(ga - ga[0])))
        print(f"dist={dist:.3e} rep={rep}")
        """,
        n_devices=8,
    )
    fields = dict(kv.split("=") for kv in out.strip().splitlines()[-1].split())
    assert float(fields["dist"]) <= 1e-5
    assert float(fields["rep"]) == 0.0
