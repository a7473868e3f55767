"""Streaming subspace service: accumulator oracle, continuity, parity.

Four layers of coverage for ``repro.stream`` (DESIGN.md §10):

1. **Streaming-equivalence oracle** — the same rows fed in k chunks land
   on the covariance ``empirical_covariance`` computes one-shot:
   bit-for-bit in f64 on integer-valued rows (every partial sum is an
   exact integer, so chunking cannot move a single bit), and <= 1e-6 in
   f32 (addition-order error only).
2. **Refresh continuity** — consecutive refreshes with the previously
   served basis as ``ref`` never sign/rotation-flip: a same-state
   re-refresh reproduces the basis element-wise to ``PARITY_TOL[32]``,
   stationary-stream jumps stay an order of magnitude under the
   smallest possible flip (``||v - (-v)||_F = 2`` per column), and the
   drift metric separates a stationary stream (~1e-7) from a rotated
   spectrum (~1e-1) — the positive control for the refresh trigger.
3. **m=8 parity cube** (slow) — streamed ingestion + cadence refreshes
   on stationary data match the serial survivor oracle across
   (psum, ring, hier) x comm_bits in {32, 8}, through a mid-stream
   membership change.  Tolerance is bit-keyed ``PARITY_TOL[bits]``: at
   32 bits the Procrustes average is exactly ref-invariant
   (polar(A R) = polar(A) R), so stream-vs-oneshot agree to ~2e-6 at
   the tested row counts; at 8 bits the stochastic-rounding noise *is*
   ref-dependent (the stream aligns to the previously served basis, the
   oracle to shard 0's), so the cells agree only to the quantization
   floor.

The hypothesis property suite for the accumulator algebra is the
sibling module tests/test_stream_properties.py (module-level
importorskip, like the other property suites).

The steady-state query path is also pinned collective-free on the jaxpr
(the service's zero-collective serving claim).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import jaxpr_primitives, run_with_devices, subspace_dist64

from repro.comm import PARITY_TOL
from repro.core.covariance import empirical_covariance
from repro.data import synthetic as syn
from repro.launch.mesh import make_aggregation_mesh
from repro.stream import Accumulator, init_state, merge, to_cov, update
from repro.stream.service import SubspaceService, basis_jump

pytestmark = pytest.mark.streaming

COLLECTIVES = {
    "psum", "all_gather", "all_to_all", "ppermute", "pmin", "pmax",
    "collective_permute", "reduce_scatter", "all_reduce",
}


def _int_rows(seed: int, n: int, d: int, dtype=np.float64) -> np.ndarray:
    """Integer-valued rows: every Gram partial sum is an exact integer,
    so any chunking of the accumulation is bit-for-bit reproducible."""
    rng = np.random.default_rng(seed)
    return rng.integers(-8, 9, size=(n, d)).astype(dtype)


def _chunks(x, k):
    return np.array_split(x, k)


# ---------------------------------------------------------------------------
# 1. Streaming-equivalence oracle
# ---------------------------------------------------------------------------


def test_chunked_equals_oneshot_bitwise_f64():
    """k-chunked accumulation == one-shot empirical_covariance, every bit."""
    with jax.enable_x64():
        x = _int_rows(0, n=257, d=24)
        want = np.asarray(empirical_covariance(jnp.asarray(x)))
        assert want.dtype == np.float64
        for k in (1, 2, 5, 8):
            acc = Accumulator(d=24, dtype=jnp.float64)
            for c in _chunks(x, k):
                acc.update(jnp.asarray(c))
            got = np.asarray(acc.to_cov())
            assert got.dtype == np.float64
            # Bit-for-bit: compare the raw bit patterns, not a tolerance.
            assert np.array_equal(
                got.view(np.uint64), want.view(np.uint64)
            ), f"k={k}: chunked f64 accumulation moved bits"


def test_chunked_equals_oneshot_f32():
    """f32 chunking only reorders additions: <= 1e-6 of the one-shot Gram."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((513, 32)).astype(np.float32)
    want = np.asarray(empirical_covariance(jnp.asarray(x)))
    for k in (3, 7):
        acc = Accumulator(d=32)
        for c in _chunks(x, k):
            acc.update(jnp.asarray(c))
        np.testing.assert_allclose(np.asarray(acc.to_cov()), want, atol=1e-6)


def test_merge_equals_concat():
    """merge(a, b) over disjoint row sets == one accumulator over the union."""
    x = _int_rows(2, 96, 16, np.float32)
    a = Accumulator(d=16).update(jnp.asarray(x[:40]))
    b = Accumulator(d=16).update(jnp.asarray(x[40:]))
    both = Accumulator(d=16).update(jnp.asarray(x))
    a.merge(b)
    assert int(a.count) == 96
    np.testing.assert_array_equal(np.asarray(a.to_cov()),
                                  np.asarray(both.to_cov()))


def test_centered_covariance_matches_numpy():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((400, 12)).astype(np.float32) + 2.5
    acc = Accumulator(d=12).update(jnp.asarray(x))
    want = (x.T @ x) / 400 - np.outer(x.mean(0), x.mean(0))
    np.testing.assert_allclose(np.asarray(acc.to_cov(center=True)), want,
                               atol=1e-5)


def test_accumulator_guards():
    acc = Accumulator(d=8)
    with pytest.raises(ValueError, match="empty accumulator"):
        acc.to_cov()
    with pytest.raises(ValueError, match=r"\(n, 8\) chunk"):
        acc.update(jnp.zeros((4, 9)))
    with pytest.raises(ValueError, match="different feature dims"):
        merge(init_state(8), init_state(9))
    with pytest.raises(ValueError, match="f32 or f64"):
        init_state(8, dtype=jnp.bfloat16)


# Property tests (hypothesis) live in tests/test_stream_properties.py,
# behind the same module-level importorskip guard as the other property
# suites — this module must run without the 'test' extra.


# ---------------------------------------------------------------------------
# 2. Refresh continuity + drift (single-device service)
# ---------------------------------------------------------------------------


def _spiked_stream(seed, d, r, n, delta=0.2):
    tau = syn.spectrum_m1(d, r, delta=delta)
    _, _, factor = syn.covariance_from_spectrum(jax.random.PRNGKey(seed), tau)
    return factor, syn.sample_gaussian(jax.random.PRNGKey(seed + 1), factor, n)


def _fed_service(d=96, r=4, steps=8, nper=512, **kw):
    mesh = make_aggregation_mesh()
    _, rows = _spiked_stream(0, d, r, steps * nper)
    svc = SubspaceService(mesh, d, r, cadence=kw.pop("cadence", 1), **kw)
    jumps = []
    for t in range(steps):
        svc.observe(rows[t * nper:(t + 1) * nper][None])
        if svc.stats["last_jump"] is not None:
            jumps.append(svc.stats["last_jump"])
    return svc, jumps


def test_refresh_continuity_stationary():
    """The continuity contract, two ways.  (a) A same-state re-refresh
    (identical covariances, ref = served basis) reproduces the basis
    element-wise to the exact-wire tolerance — any sign/rotation flip
    would register as ||v - vQ||_F >= 2 per flipped column.  (b) Across
    a stationary stream, every refresh-over-refresh jump stays an order
    of magnitude below that flip floor (the jumps are genuine sampling
    convergence, decaying as rows accumulate)."""
    svc, jumps = _fed_service()
    v0 = svc.basis
    svc.refresh()  # same accumulated state, ref = v0
    assert float(basis_jump(v0, svc.basis)) <= PARITY_TOL[32]
    assert jumps, "cadence=1 stream should have refreshed repeatedly"
    assert max(jumps) <= 0.5, (
        f"stationary refresh jumped {max(jumps):.3f} — a flip (>= 2.0) or "
        "a broken ref chain"
    )
    # The jumps shrink as the estimate converges: last < first.
    assert jumps[-1] < jumps[0]


def test_drift_metric_separates_stationary_from_shifted():
    """Positive control for the refresh trigger: a rotated spectrum pushes
    the drift metric orders of magnitude above its stationary floor."""
    d, r, nper = 96, 4, 512
    svc, _ = _fed_service(d=d, r=r)
    assert svc.drift() <= 1e-4
    svc.cadence = 10**9  # freeze refreshes; watch the metric alone
    q = syn.random_orthogonal(jax.random.PRNGKey(7), d)
    factor, _ = _spiked_stream(0, d, r, 1)
    shifted = syn.sample_gaussian(
        jax.random.PRNGKey(8), factor, 8 * nper) @ q.T
    for t in range(8):
        svc.observe(shifted[t * nper:(t + 1) * nper][None])
    assert svc.drift() >= 0.05


def test_drift_threshold_triggers_refresh():
    """With drift_threshold set, the shifted stream forces a refresh ahead
    of the (infinite) cadence."""
    d, r, nper = 64, 4, 512
    mesh = make_aggregation_mesh()
    _, rows = _spiked_stream(0, d, r, 4 * nper)
    svc = SubspaceService(mesh, d, r, cadence=10**9, drift_threshold=0.05)
    for t in range(4):
        svc.observe(rows[t * nper:(t + 1) * nper][None])
    base = svc.stats["refreshes"]  # just the bootstrap refresh
    q = syn.random_orthogonal(jax.random.PRNGKey(9), d)
    factor, _ = _spiked_stream(0, d, r, 1)
    shifted = syn.sample_gaussian(
        jax.random.PRNGKey(10), factor, 8 * nper) @ q.T
    for t in range(8):
        svc.observe(shifted[t * nper:(t + 1) * nper][None])
    assert svc.stats["refreshes"] > base, "drift trigger never fired"
    assert svc.stats["events"] == []  # drift refreshes are not replans


def test_service_stats_and_guards():
    svc = SubspaceService(make_aggregation_mesh(), 32, 2, cadence=4)
    with pytest.raises(RuntimeError, match="no basis served"):
        svc.project(jnp.zeros((1, 32)))
    with pytest.raises(ValueError, match="observe"):
        svc.refresh()
    with pytest.raises(ValueError, match="cadence"):
        SubspaceService(make_aggregation_mesh(), 32, 2, cadence=0)
    _, rows = _spiked_stream(4, 32, 2, 6 * 64)
    for t in range(6):
        svc.observe(rows[t * 64:(t + 1) * 64][None])
    s = svc.stats
    assert s["step"] == 6 and s["rows_seen"] == 6 * 64
    # bootstrap at step 1, cadence refresh at step 5 -> staleness 1
    assert s["refreshes"] == 2 and s["staleness"] == 1
    out = svc.project(rows[:10])
    assert out.shape == (10, 2)


@pytest.mark.parametrize("with_ref", [False, True],
                         ids=["bootstrap", "steady"])
def test_refresh_reuses_the_from_covs_program(with_ref):
    """A refresh runs ``distributed_pca_from_covs``'s program: after a
    ``from_covs`` call with the service's plan and membership, the
    refresh builds no program and serves the same basis, bit for bit."""
    from repro.core.distributed import (
        _mesh_program,
        distributed_pca_from_covs,
    )
    from repro.stream.service import _safe_covs

    d, r, nper = 40, 3, 256
    mesh = make_aggregation_mesh()
    _, rows = _spiked_stream(3, d, r, 2 * nper)
    svc = SubspaceService(mesh, d, r, cadence=1)
    if with_ref:
        svc.observe(rows[:nper][None])  # the bootstrap refresh
    ref = svc.basis
    kw = dict(n_iter=svc.n_iter, solver=svc.solver, iters=svc.iters,
              plan=svc.plan, membership=svc.membership, ref=ref)
    distributed_pca_from_covs(
        empirical_covariance(rows[nper:])[None], mesh, r, **kw)
    info = _mesh_program.cache_info()
    svc.observe(rows[nper:][None])
    after = _mesh_program.cache_info()
    assert (after.hits, after.misses) == (info.hits + 1, info.misses)
    want = distributed_pca_from_covs(_safe_covs(svc._state), mesh, r, **kw)
    np.testing.assert_array_equal(np.asarray(svc.basis), np.asarray(want))


def test_query_path_has_zero_collectives():
    """The serving claim: the steady-state query program is a replicated
    matmul — no collective primitive anywhere in its jaxpr."""
    svc = SubspaceService(make_aggregation_mesh(), 48, 4)
    jxp = jax.make_jaxpr(svc.query_fn)(
        jnp.zeros((64, 48)), jnp.zeros((48, 4))
    )
    prims = set(jaxpr_primitives(jxp))
    assert not prims & COLLECTIVES, prims & COLLECTIVES


def test_bench_stream_check_gate_math():
    """The bench gate's arithmetic: amortized refresh vs one query batch,
    min-of-reps on both sides, tolerant of missing stream-query cells."""
    from benchmarks import bench_stream as B

    def cell(workload, wall_min, **kw):
        rec = {"workload": workload, "m": 8, "d": 64, "r": 4,
               "wall_us": wall_min * 1.3, "wall_us_min": wall_min}
        rec.update(kw)
        return rec

    doc = {"meta": {"cadence": 4}, "records": [
        cell("stream-query", 100.0),
        cell("stream-refresh", 300.0, comm="psum", pods=0, bits=32),
        cell("stream-refresh", 5000.0, comm="ring", pods=0, bits=8),
        cell("stream-refresh", 999999.0, comm="psum", pods=0, bits=32, d=128),
    ]}
    bad, checked = B.check(doc, max_overhead=4.0)
    # 300/4 = 75 <= 400 passes; 5000/4 = 1250 > 400 fails; the d=128
    # refresh has no matching query cell and is skipped, not crashed.
    assert checked == 2
    assert len(bad) == 1 and bad[0]["comm"] == "ring"
    assert bad[0]["amortized_us"] == pytest.approx(1250.0)


# ---------------------------------------------------------------------------
# 3. m=8 parity cube (subprocess; slow lane)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_stream_matches_oneshot_cube_eight_devices():
    """Acceptance: streamed ingestion + cadence refreshes on stationary
    data land on the one-shot estimate, across (psum, ring, hier) x
    comm_bits in {32, 8}, *through* a mid-stream membership change
    (shard 2 dies halfway; the service replans, refreshes immediately,
    and keeps streaming over the survivors).

    Oracle: the serial refinement round over the survivors' full-stream
    covariances.  Tolerance is bit-keyed: exact-wire cells sit near the
    second-order ref-dependence floor (~2e-6 at these row counts — see
    the nper note in the snippet); 8-bit cells carry stochastic-rounding
    noise that depends on the alignment reference — the stream refreshes
    against the previously *served* basis while the one-shot oracle is
    reference-free — so they are only comparable at the PARITY_TOL[8]
    quantization floor.
    """
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from repro.comm import Membership
        from repro.core.covariance import empirical_covariance
        from repro.core.eigenspace import refinement_rounds
        from repro.core.metrics import subspace_dist64
        from repro.core.subspace import local_eigenbasis
        from repro.data import synthetic as syn
        from repro.launch.mesh import make_aggregation_mesh
        from repro.stream import SubspaceService

        # nper matters: the Procrustes average's residual dependence on
        # the alignment reference is second order (local spread x ref
        # subspace offset, both ~ 1/sqrt(n)), so the 32-bit stream/oracle
        # gap scales ~ 1/n.  2048 rows/step lands it at ~2e-6, safely
        # under the 1e-5 acceptance bound; 64 rows/step sits at ~1e-3.
        m, d, r, steps, nper = 8, 96, 4, 8, 2048
        kill_at, dead = steps // 2, (2,)
        tau = syn.spectrum_m1(d, r, delta=0.2)
        _, _, factor = syn.covariance_from_spectrum(
            jax.random.PRNGKey(0), tau)
        rows = syn.sample_gaussian(
            jax.random.PRNGKey(1), factor, m * steps * nper
        ).reshape(steps, m, nper, d)
        mem = Membership.from_dead(m, dead)

        # Serial oracle: survivors' covariances over their full stream,
        # local eigenbasis, one refinement round (n_iter=1).
        keep = jnp.asarray(mem.indices)
        full = rows.transpose(1, 0, 2, 3).reshape(m, steps * nper, d)
        covs = jnp.stack([empirical_covariance(full[i]) for i in range(m)])
        vs = jnp.stack(
            [local_eigenbasis(covs[i], r, method="eigh")[0]
             for i in range(m)])
        ser = refinement_rounds(vs[keep], n_iter=1)

        for topo in ("psum", "ring", "hier"):
            pods = 4 if topo == "hier" else None
            mesh = make_aggregation_mesh(m, pods=pods)
            for cb in (32, 8):
                svc = SubspaceService(
                    mesh, d, r, cadence=2, topology=topo, comm_bits=cb)
                for t in range(steps):
                    if t == kill_at:
                        svc.set_membership(mem)
                    svc.observe(rows[t])
                if svc.stats["staleness"]:
                    svc.refresh()
                dist = float(subspace_dist64(ser, svc.basis))
                ev = ",".join(svc.stats["events"])
                print("CELL", topo, cb, dist, svc.stats["replans"], ev)
        """,
        n_devices=8,
    )
    cells = [ln.split() for ln in out.strip().splitlines()
             if ln.startswith("CELL")]
    assert len(cells) == 6
    for _, topo, cb, dist, replans, events in cells:
        tol = max(1e-5, PARITY_TOL[int(cb)])
        assert float(dist) <= tol, (topo, cb, dist)
        assert int(replans) == 1 and events == "failure", (topo, events)
    # The exact-wire cells must sit at the paper tolerance regardless of
    # topology — the ref-chained stream is not allowed to drift off the
    # one-shot answer.
    for _, topo, cb, dist, *_ in cells:
        if int(cb) == 32:
            assert float(dist) <= 1e-5, (topo, dist)
