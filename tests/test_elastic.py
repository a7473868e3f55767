"""Elastic aggregation runtime: membership masking, re-planning, recovery.

The semantic contract under test (DESIGN.md §Failure model): **a masked
round over the survivors is the round a fresh m'-shard job would run** on
the survivors' data.  Fast lane — the ``Membership`` mask itself, the
masked cost model, the ``replan`` hook's verbatim equivalence to
``plan_aggregation(m=m')``, the traced program actually shrinking
(ppermute count), and the straggler → re-plan wiring.  Slow lane
(subprocess, 8 fake devices) — the masked parity cube against the serial
oracle restricted to the survivors, a mid-run kill through
``elastic_pca`` against the composed oracle, the recovery path, and the
masked ring's compiled HLO bytes against ``comm_cost(membership=)``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import jaxpr_primitives, run_with_devices, subspace_dist64

from repro.comm import PARITY_TOL, Membership, comm_cost, resolve_membership


# ---------------------------------------------------------------------------
# Membership: the jit-static mask.


def test_membership_basics():
    mem = Membership.from_dead(4, (2,))
    assert mem.m == 4
    assert mem.m_active == 3
    assert not mem.is_full
    assert mem.indices == (0, 1, 3)
    assert mem.dead == (2,)
    assert mem.first_active == 0


def test_membership_full_and_none_agree():
    assert Membership.full(3) == Membership(active=(True, True, True))
    assert Membership.full(3).is_full
    assert resolve_membership(None, 3) == Membership.full(3)


def test_membership_first_active_skips_dead_shard_zero():
    mem = Membership.from_dead(4, (0, 1))
    assert mem.first_active == 2


def test_membership_drop_recover_roundtrip():
    mem = Membership.full(5).drop(1, 3)
    assert mem.dead == (1, 3)
    assert mem.drop(1) == mem  # idempotent
    back = mem.recover(3)
    assert back.dead == (1,)
    assert back.recover(1) == Membership.full(5)


def test_membership_validation():
    with pytest.raises(ValueError):
        Membership(active=())
    with pytest.raises(ValueError):
        Membership(active=(False, False))  # no survivors
    with pytest.raises(ValueError):
        Membership.from_dead(4, (4,))  # out of range
    with pytest.raises(ValueError):
        Membership.full(4).recover(9)


def test_membership_is_hashable_and_static():
    """Frozen + tuple-backed: usable as a jit closure constant / dict key,
    and truthy inputs normalize to bools (1 == True hashes identically)."""
    a = Membership(active=(1, 0, 1))
    b = Membership(active=(True, False, True))
    assert a == b and hash(a) == hash(b)
    assert {a: "x"}[b] == "x"


def test_resolve_membership_errors():
    with pytest.raises(TypeError):
        resolve_membership((True, True), 2)  # must be Membership or None
    with pytest.raises(ValueError):
        resolve_membership(Membership.full(4), 8)  # wrong axis size


# ---------------------------------------------------------------------------
# Masked cost model: the physical wire, as compiled.


def test_comm_cost_masked_ring_shrinks_to_survivor_hops():
    m, d, r, n = 8, 64, 4, 2
    mem = Membership.from_dead(m, (2,))
    msg = d * r * 32
    cost = comm_cost("ring", m=m, d=d, r=r, n_iter=n, membership=mem)
    # n rounds of m'-1 survivor hops, the initial reference broadcast,
    # and one exact f32 resync broadcast so dead shards leave holding the
    # survivors' basis.
    assert cost.hlo_bits["collective-permute"] == n * (mem.m_active - 1) * msg
    assert cost.hlo_bits["all-reduce"] == msg + d * r * 32


def test_comm_cost_masked_psum_gather_unchanged():
    """psum / gather still run over the full physical axis (masked zeros /
    dropped rows), so their per-device wire bytes do not move."""
    m, d, r = 8, 64, 4
    mem = Membership.from_dead(m, (2,))
    for topo in ("psum", "gather"):
        full = comm_cost(topo, m=m, d=d, r=r, n_iter=2)
        masked = comm_cost(topo, m=m, d=d, r=r, n_iter=2, membership=mem)
        assert masked.hlo_bits == full.hlo_bits
        assert masked.bits == full.bits


def test_comm_cost_full_membership_is_noop():
    for topo in ("psum", "gather", "ring"):
        a = comm_cost(topo, m=8, d=64, r=4, n_iter=2)
        b = comm_cost(
            topo, m=8, d=64, r=4, n_iter=2, membership=Membership.full(8)
        )
        assert a == b


# ---------------------------------------------------------------------------
# Planning at m': the re-plan hook.


def test_replan_is_plan_aggregation_at_survivor_count():
    """Acceptance: the hook's Plan is ``plan_aggregation(m=m')`` verbatim."""
    from repro.plan import plan_aggregation
    from repro.runtime.elastic import replan

    mem = Membership.from_dead(8, (2,))
    for kwargs in (
        dict(),
        dict(topology="ring", comm_bits=8),
        dict(ref_broadcast=False, n_iter=3),
    ):
        got = replan(mem, d=256, r=8, **kwargs)
        want = plan_aggregation(m=7, d=256, r=8, **kwargs)
        assert got == want, kwargs


def test_replan_rechecks_int8_psum_headroom():
    """int8 psum needs m <= 126 contributors: above that, a comm_bits=8
    re-plan must route around the psum cell."""
    from repro.runtime.elastic import replan

    big = Membership.from_dead(150, (0,))  # m' = 149 > 126
    pl = replan(big, d=256, r=8, comm_bits=8)
    assert not (pl.topology == "psum" and pl.comm_bits == 8)
    ok = Membership.from_dead(8, (2,))  # m' = 7: psum int8 is feasible
    pl = replan(ok, d=256, r=8, comm_bits=8, topology="psum")
    assert (pl.topology, pl.comm_bits) == ("psum", 8)


def test_resolve_plan_full_membership_identity():
    """membership=None and an explicit full mask resolve the same Plan —
    the legacy program is byte-identical."""
    from repro.plan import resolve_plan

    a = resolve_plan(None, m=8, d=256, r=8, n_iter=2)
    b = resolve_plan(
        None, m=8, d=256, r=8, n_iter=2, membership=Membership.full(8)
    )
    assert a == b


def test_resolve_plan_auto_prices_at_survivor_count():
    from repro.plan import plan_aggregation, resolve_plan

    mem = Membership.from_dead(8, (2,))
    degraded = resolve_plan("auto", m=8, d=256, r=8, n_iter=2, membership=mem)
    fresh = plan_aggregation(m=7, d=256, r=8, n_iter=2)
    assert (degraded.topology, degraded.comm_bits, degraded.backend) == (
        fresh.topology, fresh.comm_bits, fresh.backend,
    )


# ---------------------------------------------------------------------------
# The traced program genuinely shrinks: survivor-only ring permutation.


def test_masked_ring_traces_survivor_hops_only():
    from repro.core.distributed import procrustes_average_collective

    m, d, r = 4, 64, 4

    def ring(v, membership=None):
        return procrustes_average_collective(
            v, axis_name="data", n_iter=1, topology="ring", ring_chunk=d,
            membership=membership,
        )

    v = jnp.zeros((d, r))
    axis_env = [("data", m)]
    full = jaxpr_primitives(
        jax.make_jaxpr(ring, axis_env=axis_env)(v)
    )
    mem = Membership.from_dead(m, (1,))
    masked = jaxpr_primitives(
        jax.make_jaxpr(lambda v: ring(v, mem), axis_env=axis_env)(v)
    )
    # One chunk per hop at ring_chunk=d: hop count IS the ppermute count.
    assert full.count("ppermute") == m - 1
    assert masked.count("ppermute") == mem.m_active - 1
    # The masked program adds the resync broadcast (a psum) at the end.
    assert masked.count("psum") > full.count("psum")


# ---------------------------------------------------------------------------
# The elastic runner (single device lanes).


def _samples(m, n, d, seed=0):
    key = jax.random.PRNGKey(seed)
    return jax.random.normal(key, (m * n, d))


def test_elastic_pca_matches_distributed_pca_when_healthy():
    """No injector, no monitor: elastic_pca is distributed_pca plus a
    decision log with the single 'initial' event."""
    from repro.launch.mesh import make_mesh
    from repro.core.distributed import distributed_pca
    from repro.runtime.elastic import elastic_pca

    mesh = make_mesh((1,), ("data",))
    d, r = 48, 4
    samples = _samples(1, 256, d)
    report = elastic_pca(samples, mesh, r, n_iter=2, solver="eigh")
    base = distributed_pca(samples, mesh, r, n_iter=2, solver="eigh")
    assert subspace_dist64(report.basis, base) < 1e-6
    assert [e.reason for e in report.events] == ["initial"]
    assert report.replans == 0
    assert report.rounds == 2
    assert report.final_membership == Membership.full(1)


def test_elastic_pca_straggler_escalation_replans():
    """A slow group trips the monitor; the pending re-plan is honoured at
    the next group boundary and the user's own callback still fires."""
    from repro.launch.mesh import make_mesh
    from repro.runtime.elastic import elastic_pca
    from repro.runtime.straggler import StragglerMonitor

    class FakeTimer:
        def lap(self):
            return 1.0  # every group reads as pathologically slow

    hits = []
    mon = StragglerMonitor(
        warmup=0, patience=1, threshold=0.0,
        on_escalate=lambda s, dt: hits.append((s, dt)),
    )
    mesh = make_mesh((1,), ("data",))
    report = elastic_pca(
        _samples(1, 128, 32), mesh, 4, n_iter=3, solver="eigh",
        monitor=mon, timer=FakeTimer(), max_group=1,
    )
    reasons = [e.reason for e in report.events]
    assert reasons[0] == "initial"
    assert "straggler" in reasons
    assert report.replans >= 1
    assert hits  # the user callback was chained, not replaced


def test_eigen_compress_config_with_membership_is_hashable():
    from repro.optim.eigen_compress import EigenCompressConfig

    cfg = EigenCompressConfig(membership=Membership.from_dead(4, (1,)))
    assert isinstance(hash(cfg), int)
    assert cfg.membership.m_active == 3


def test_check_aggregate_is_membership_agnostic():
    """The perf gate keys and groups by membership: a degraded-mesh
    record never joins against — or gets gated with — a full-membership
    cell, so masked records cannot flake the gate (and v4 files upgrade
    with membership="full")."""
    from benchmarks import bench_aggregate as A

    assert "membership" in A.KEY_FIELDS

    def rec(membership, wall):
        return {
            "topology": "collective", "comm": "ring", "bits": 32,
            "membership": membership, "backend": "xla", "polar": "svd",
            "orth": "qr", "m": 8, "d": 128, "r": 4, "n_iter": 2,
            "mode": "compiled", "wall_us": wall, "wall_us_min": wall,
            "compile_s": 0.1, "reps": 3,
        }

    meta = {"platform": "cpu"}
    old = {"schema": A.SCHEMA, "meta": meta,
           "records": [rec("full", 100.0)]}
    # The new sweep's only matching-key record is fine; the masked record
    # is 100x slower but has no baseline cell and its own group.
    new = {"schema": A.SCHEMA, "meta": meta,
           "records": [rec("full", 100.0), rec("dead=[2]", 10000.0)]}
    regressions, checked = A.check(old, new)
    assert checked == 1  # the masked record did not join the full cell
    assert regressions == []


def test_bench_aggregate_v4_upgrades_with_full_membership(tmp_path):
    import json

    from benchmarks import bench_aggregate as A

    doc = {"schema": A.SCHEMA_V4, "meta": {"platform": "cpu"},
           "records": [{"topology": "stacked", "comm": "-", "bits": 32}]}
    p = tmp_path / "v4.json"
    p.write_text(json.dumps(doc))
    up = A.load(str(p))
    assert up["schema"] == A.SCHEMA
    assert up["records"][0]["membership"] == "full"


@pytest.mark.slow
def test_dryrun_drop_shards_records_membership(tmp_path):
    """--drop-shards lowers the degraded-mesh program and the record says
    so — the membership-keyed cell the perf gate groups separately."""
    import json
    import os
    import subprocess
    import sys

    from conftest import SRC

    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--paper-pca",
         "--single-pod", "--topology", "ring", "--drop-shards", "1",
         "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.load(open(os.path.join(
        str(tmp_path), "paper-pca__pca__singlepod.json")))
    assert rec["membership"] == "dead=[1]"
    # Reduced single-pod mesh is (2, n//2): the data axis has 2 shards.
    assert rec["m_active"] == 1
    from repro.configs.paper_pca import CONFIG as pcfg

    cost = comm_cost(
        "ring", m=2, d=pcfg.d, r=pcfg.r, n_iter=pcfg.n_iter,
        membership=Membership.from_dead(2, (1,)),
    )
    assert rec["predicted_collective_bits"] == cost.bits


# ---------------------------------------------------------------------------
# Slow lane: 8 fake devices in a subprocess.


@pytest.mark.slow
def test_masked_parity_cube_eight_devices():
    """Acceptance: shard 2 dead from round 0 at m=8 — every (topology x
    comm_bits) cell matches the serial oracle restricted to the 7
    survivors within PARITY_TOL[bits], on noisy-copy stacks (the regime
    the tolerances were calibrated on).  The dead shard's output row is
    asserted too: every topology leaves the answer replicated (the masked
    ring via its explicit resync broadcast)."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.comm import Membership
        from repro.core import refinement_rounds
        from repro.core.distributed import procrustes_average_collective
        from repro.core.metrics import subspace_dist64

        m, d, r = 8, 96, 4
        mem = Membership.from_dead(m, (2,))
        u = jnp.linalg.qr(
            jax.random.normal(jax.random.PRNGKey(53), (d, r)))[0]
        noise = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (m, d, r))
        vs = jnp.linalg.qr(u[None] + noise)[0]
        ser = refinement_rounds(vs[jnp.asarray(mem.indices)], n_iter=2)
        mesh = make_mesh((m,), ("data",))
        for topo in ("psum", "gather", "ring"):
            for cb in (32, 16, 8):
                fn = jax.jit(shard_map(
                    lambda v, t=topo, b=cb: procrustes_average_collective(
                        v[0], axis_name="data", n_iter=2, topology=t,
                        comm_bits=b, membership=mem)[None],
                    mesh=mesh, in_specs=P("data", None, None),
                    out_specs=P("data", None, None), check_vma=False,
                ))
                got = fn(vs)
                d_live = float(subspace_dist64(ser, got[0]))
                d_dead = float(subspace_dist64(ser, got[2]))
                print("CELL", topo, cb, d_live, d_dead)
        """
    )
    from repro.comm import PARITY_TOL

    cells = [ln.split() for ln in out.strip().splitlines()
             if ln.startswith("CELL")]
    assert len(cells) == 9
    for _, topo, cb, d_live, d_dead in cells:
        tol = PARITY_TOL[int(cb)]
        assert float(d_live) <= tol, (topo, cb, d_live)
        assert float(d_dead) <= tol, (topo, cb, d_dead)


@pytest.mark.slow
def test_elastic_midrun_kill_matches_composed_oracle():
    """Acceptance: kill shard 2 before round 2 of 4 — the elastic run over
    m'=7 survivors equals the composed serial oracle (2 full rounds, then
    2 survivor rounds refining the round-2 basis as reference) within the
    exact-wire tolerance, for every topology.  The failure event's Plan
    must be ``plan_aggregation(m=7)`` at the remaining rounds, verbatim."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.comm import Membership
        from repro.core import refinement_rounds
        from repro.core.distributed import _local_pca_basis
        from repro.core.metrics import subspace_dist64
        from repro.plan import plan_aggregation
        from repro.runtime.elastic import elastic_pca
        from repro.runtime.fault import FailureInjector

        m, n, d, r = 8, 128, 48, 4
        samples = jax.random.normal(jax.random.PRNGKey(0), (m * n, d))
        mesh = make_mesh((m,), ("data",))
        xs = samples.reshape(m, n, d)
        vs = jnp.stack([
            _local_pca_basis(xs[i], r, solver="eigh", iters=30,
                             backend="xla") for i in range(m)])
        mem = Membership.from_dead(m, (2,))
        mid = refinement_rounds(vs, n_iter=2)
        ser = refinement_rounds(
            vs[jnp.asarray(mem.indices)], mid, n_iter=2)
        for topo in ("psum", "gather", "ring"):
            inj = FailureInjector(fail_at=((2, 2),))
            rep = elastic_pca(
                samples, mesh, r, n_iter=4, solver="eigh",
                topology=topo, injector=inj)
            dist = float(subspace_dist64(ser, rep.basis))
            ev = rep.events[1]
            want = plan_aggregation(
                m=7, d=d, r=r, n_iter=2, ref_broadcast=False,
                topology=topo)
            print("CELL", topo, dist, ev.reason, ev.round_index,
                  rep.replans, ev.plan == want,
                  rep.final_membership.m_active)
        """
    )
    cells = [ln.split() for ln in out.strip().splitlines()
             if ln.startswith("CELL")]
    assert len(cells) == 3
    for _, topo, dist, reason, rnd, replans, plan_ok, m_active in cells:
        assert float(dist) <= PARITY_TOL[32], (topo, dist)
        assert reason == "failure" and rnd == "2"
        assert int(replans) == 1
        assert plan_ok == "True", topo
        assert m_active == "7"


@pytest.mark.slow
def test_elastic_recovery_rejoins_via_alignment():
    """Kill shard 2 before round 1, recover it before round 3: the run
    logs failure then recovery, ends at full membership, and the rejoined
    estimate still matches the healthy all-alive run closely (the
    recovered shard re-aligned to the current basis, not a stale one)."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.metrics import subspace_dist64
        from repro.data import synthetic as syn
        from repro.runtime.elastic import elastic_pca
        from repro.runtime.fault import FailureInjector

        m, n, d, r = 8, 256, 48, 4
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        tau = syn.spectrum_m1(d, r, delta=0.2)
        _, u, factor = syn.covariance_from_spectrum(k1, tau)
        samples = syn.sample_gaussian(k2, factor, m * n)
        mesh = make_mesh((m,), ("data",))
        inj = FailureInjector(fail_at=((2, 1),), recover_at=((2, 3),))
        rep = elastic_pca(samples, mesh, r, n_iter=4, solver="eigh",
                          injector=inj)
        healthy = elastic_pca(samples, mesh, r, n_iter=4, solver="eigh")
        v = rep.basis
        ortho = float(jnp.abs(v.T @ v - jnp.eye(r)).max())
        print("REASONS", ",".join(e.reason for e in rep.events))
        print("FULL", rep.final_membership.is_full)
        print("ORTHO", ortho)
        print("DIST", float(subspace_dist64(healthy.basis, v)))
        print("DIST_TRUE", float(subspace_dist64(u[:, :r], v)))
        print("DIST_TRUE_HEALTHY",
              float(subspace_dist64(u[:, :r], healthy.basis)))
        """
    )
    lines = dict(
        ln.split(None, 1) for ln in out.strip().splitlines()
        if ln.strip()
    )
    assert lines["REASONS"] == "initial,failure,recovery"
    assert lines["FULL"] == "True"
    assert float(lines["ORTHO"]) < 1e-4
    # Spiked-covariance data (the paper's setting): every shard's local
    # basis estimates the same true subspace, so one shard sitting out
    # two of four rounds barely moves the answer — and the degraded run
    # must stay about as close to the truth as the healthy one (a stale,
    # unaligned rejoin would wreck both bounds).
    assert float(lines["DIST"]) < 5e-2
    assert float(lines["DIST_TRUE"]) < 2 * float(lines["DIST_TRUE_HEALTHY"]) + 1e-3


@pytest.mark.slow
def test_elastic_pca_second_call_builds_no_program():
    """The local stage and every group come from the shared program
    builder: a second run with the same shapes and fault schedule builds
    no program and returns the same basis, bit for bit."""
    out = run_with_devices(
        """
        import jax, numpy as np
        from repro.core.distributed import _mesh_program
        from repro.launch.mesh import make_mesh
        from repro.runtime.elastic import elastic_pca
        from repro.runtime.fault import FailureInjector

        m, n, d, r = 4, 64, 24, 3
        samples = jax.random.normal(jax.random.PRNGKey(0), (m * n, d))
        mesh = make_mesh((m,), ("data",))

        def run():
            inj = FailureInjector(fail_at=((2, 1),), recover_at=((2, 3),))
            return elastic_pca(samples, mesh, r, n_iter=4, solver="eigh",
                               injector=inj)

        first = run()
        before = _mesh_program.cache_info()
        second = run()
        after = _mesh_program.cache_info()
        print("REASONS", ",".join(e.reason for e in second.events))
        print("MISSES", after.misses - before.misses)
        print("HITS", after.hits - before.hits)
        print("SAME", np.array_equal(np.asarray(first.basis),
                                     np.asarray(second.basis)))
        """,
        n_devices=4,
    )
    lines = dict(ln.split(None, 1) for ln in out.strip().splitlines()
                 if ln.strip())
    assert lines["REASONS"] == "initial,failure,recovery"
    assert lines["MISSES"] == "0"
    # The local stage and one program per group of rounds.
    assert lines["HITS"] == "4"
    assert lines["SAME"] == "True"


@pytest.mark.slow
def test_masked_ring_hlo_bytes_match_cost_model():
    """The degraded ring's compiled program bills exactly what
    ``comm_cost(..., membership=)`` predicts: m'-1 survivor hops per
    round, the reference broadcast, and the one f32 resync broadcast."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.comm import Membership
        from repro.core.distributed import procrustes_average_collective
        from repro.launch.hlo_analysis import collective_bytes

        m, d, r = 8, 96, 4
        mem = Membership.from_dead(m, (2,))
        mesh = make_mesh((m,), ("data",))
        like = jax.ShapeDtypeStruct((m, d, r), jnp.float32)
        for cb in (32, 8):
            fn = jax.jit(shard_map(
                lambda v, b=cb: procrustes_average_collective(
                    v[0], axis_name="data", n_iter=2, topology="ring",
                    comm_bits=b, membership=mem)[None],
                mesh=mesh, in_specs=P("data", None, None),
                out_specs=P("data", None, None), check_vma=False,
            ))
            hlo = collective_bytes(fn.lower(like).compile().as_text())
            print("CELL", cb,
                  json.dumps({k: v for k, v in hlo.items() if v}))
        """
    )
    import json

    cells = [ln.split(None, 2) for ln in out.strip().splitlines()
             if ln.startswith("CELL")]
    assert len(cells) == 2
    mem = Membership.from_dead(8, (2,))
    for _, cb, blob in cells:
        predicted = {
            k: v
            for k, v in comm_cost(
                "ring", m=8, d=96, r=4, n_iter=2, comm_bits=int(cb),
                membership=mem,
            ).hlo_bytes.items()
            if v
        }
        assert json.loads(blob) == predicted, cb
