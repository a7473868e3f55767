"""Distributed (shard_map) runtime == serial reference, on real fake meshes.

Multi-device cases run in a subprocess with
``--xla_force_host_platform_device_count`` so the main test process keeps a
single device (per the project rules).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices

from repro.launch.mesh import make_mesh
from repro.core import (
    distributed_pca,
    distributed_pca_from_covs,
    empirical_covariance,
    local_bases,
    procrustes_fix_average,
)
from repro.data import synthetic as syn


def test_single_device_mesh_identity():
    """On a 1-device mesh, distributed PCA == local PCA of the full data."""
    mesh = make_mesh((1,), ("data",))
    key = jax.random.PRNGKey(0)
    tau = syn.spectrum_m1(48, 3, delta=0.2)
    _, u, factor = syn.covariance_from_spectrum(key, tau)
    samples = syn.sample_gaussian(jax.random.PRNGKey(1), factor, 256)
    v = distributed_pca(samples, mesh, 3)
    cov = empirical_covariance(samples)
    vs = local_bases(cov[None], 3)
    ref = procrustes_fix_average(vs)
    np.testing.assert_allclose(np.asarray(v), np.asarray(ref), atol=1e-5)


@pytest.mark.slow
def test_eight_device_matches_serial():
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core import (distributed_pca, empirical_covariance,
                                local_bases, procrustes_fix_average,
                                iterative_refinement)
        from repro.data import synthetic as syn
        mesh = make_mesh((8,), ("data",))
        key = jax.random.PRNGKey(0)
        d, r, m, n = 96, 4, 8, 200
        tau = syn.spectrum_m1(d, r, delta=0.2)
        _, u, factor = syn.covariance_from_spectrum(key, tau)
        samples = syn.sample_gaussian(jax.random.PRNGKey(1), factor, m * n)
        v_dist = distributed_pca(samples, mesh, r, n_iter=1)
        xs = samples.reshape(m, n, d)
        covs = jax.vmap(lambda x: empirical_covariance(x))(xs)
        vs = local_bases(covs, r)
        v_ser = procrustes_fix_average(vs)
        print("ERR1", float(jnp.linalg.norm(v_dist - v_ser)))
        v_d2 = distributed_pca(samples, mesh, r, n_iter=3)
        v_s2 = iterative_refinement(vs, n_iter=3)
        print("ERR2", float(jnp.linalg.norm(v_d2 - v_s2)))
        v_p = distributed_pca(samples, mesh, r, n_iter=1, backend="pallas")
        print("ERR3", float(jnp.linalg.norm(v_p - v_ser)))
        """
    )
    errs = {
        line.split()[0]: float(line.split()[1])
        for line in out.strip().splitlines()
        if line.startswith("ERR")
    }
    assert errs["ERR1"] < 1e-4
    assert errs["ERR2"] < 1e-4
    # all-gather + Pallas-kernel topology == psum topology == serial reference
    assert errs["ERR3"] < 1e-4


@pytest.mark.slow
def test_from_covs_and_subspace_solver():
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core import (distributed_pca_from_covs, empirical_covariance,
                                local_bases, procrustes_fix_average, dist_2)
        from repro.data import synthetic as syn
        mesh = make_mesh((8,), ("data",))
        key = jax.random.PRNGKey(0)
        d, r, m, n = 64, 4, 8, 300
        tau = syn.spectrum_m1(d, r, delta=0.2)
        sigma, u, factor = syn.covariance_from_spectrum(key, tau)
        keys = jax.random.split(jax.random.PRNGKey(1), m)
        xs = jnp.stack([syn.sample_gaussian(k, factor, n) for k in keys])
        covs = jax.vmap(lambda x: empirical_covariance(x))(xs)
        v = distributed_pca_from_covs(covs, mesh, r, solver="subspace", iters=60)
        print("DIST", float(dist_2(v, u[:, :r])))
        """
    )
    val = float(out.strip().splitlines()[-1].split()[1])
    assert val < 0.3


# -- the program cache ------------------------------------------------------

D, R = 32, 3


def _cache():
    from repro.core.distributed import _mesh_program

    info = _mesh_program.cache_info()
    return info.hits, info.misses


def _fresh_program(x, mesh, r, *, n_iter=1, solver="eigh", iters=30,
                   plan=None, membership=None, from_covs=False,
                   with_ref=False):
    """``distributed_pca``'s (or, ``from_covs``,
    ``distributed_pca_from_covs``') program for these arguments, built
    with the cache bypassed."""
    from repro.comm.membership import resolve_membership
    from repro.core.distributed import _LocalStage, _mesh_program
    from repro.plan.planner import resolve_plan

    mem = resolve_membership(membership, mesh.shape["data"])
    pl = resolve_plan(plan, m=mem.m, d=x.shape[-1], r=r, n_iter=n_iter,
                      membership=mem)
    local = (_LocalStage("covs", r, solver, iters) if from_covs else
             _LocalStage("rows", r, solver, iters, backend=pl.backend))
    return _mesh_program.__wrapped__(
        mesh, "data", False, local, (n_iter, pl, mem), x.ndim, with_ref
    )


def _small_rows(n):
    key = jax.random.PRNGKey(0)
    _, _, factor = syn.covariance_from_spectrum(key, syn.spectrum_m1(D, R))
    return syn.sample_gaussian(jax.random.PRNGKey(1), factor, n)


def test_repeated_call_reuses_its_program_and_answer():
    mesh = make_mesh((1,), ("data",))
    x = _small_rows(128)
    v1 = distributed_pca(x, mesh, R, n_iter=2)
    hits, misses = _cache()
    v2 = distributed_pca(x, mesh, R, n_iter=2)
    assert _cache() == (hits + 1, misses)
    fresh = _fresh_program(x, mesh, R, n_iter=2)(x)[0]
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(fresh))


def test_from_covs_builds_one_program_with_ref_and_one_without():
    mesh = make_mesh((1,), ("data",))
    x = _small_rows(128)
    covs = empirical_covariance(x)[None]
    # A width no other test runs, so both programs are new here.
    r = R - 1
    ref = distributed_pca(x, mesh, R)[:, :r]
    hits, misses = _cache()
    outs = [distributed_pca_from_covs(covs, mesh, r),
            distributed_pca_from_covs(covs, mesh, r, ref=ref)]
    assert _cache() == (hits, misses + 2)
    again = [distributed_pca_from_covs(covs, mesh, r),
             distributed_pca_from_covs(covs, mesh, r, ref=ref)]
    assert _cache() == (hits + 2, misses + 2)
    fresh = [_fresh_program(covs, mesh, r, from_covs=True)(covs)[0],
             _fresh_program(covs, mesh, r, from_covs=True, with_ref=True)(
                 covs, ref)[0]]
    for a, b, c in zip(outs, again, fresh, strict=True):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(a))


# Each case changes one static input of the base call on four devices.
PROGRAM_CASES = ["r", "n_iter", "solver", "plan", "membership", "mesh"]


@pytest.fixture(scope="module")
def program_cases():
    out = run_with_devices(
        """
        import json
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.comm.membership import Membership, resolve_membership
        from repro.core.distributed import (
            _LocalStage, _mesh_program, distributed_pca)
        from repro.data import synthetic as syn
        from repro.launch.mesh import make_mesh
        from repro.plan.planner import resolve_plan

        D, R = 32, 3
        mesh = make_mesh((4,), ("data",))
        _, _, factor = syn.covariance_from_spectrum(
            jax.random.PRNGKey(0), syn.spectrum_m1(D, R))
        x = syn.sample_gaussian(jax.random.PRNGKey(1), factor, 4 * 64)
        base = dict(mesh=mesh, r=R, n_iter=1, solver="eigh", plan=None,
                    membership=None)
        cases = {
            "r": dict(r=R - 1),
            "n_iter": dict(n_iter=2),
            "solver": dict(solver="subspace"),
            "plan": dict(plan=resolve_plan(None, m=4, d=D, r=R,
                                           topology="gather")),
            "membership": dict(membership=Membership.from_dead(4, [2])),
            "mesh": dict(mesh=Mesh(np.array(jax.devices()[::-1]), ("data",))),
        }

        def cache():
            info = _mesh_program.cache_info()
            return info.hits, info.misses

        def fresh(mesh, r, n_iter, solver, plan, membership):
            mem = resolve_membership(membership, 4)
            pl = resolve_plan(plan, m=4, d=D, r=r, n_iter=n_iter,
                              membership=mem)
            local = _LocalStage("rows", r, solver, 30, backend=pl.backend)
            return _mesh_program.__wrapped__(
                mesh, "data", False, local, (n_iter, pl, mem), 2)(x)[0]

        def call(kw):
            kw = dict(kw)
            return distributed_pca(x, kw.pop("mesh"), kw.pop("r"), **kw)

        v0 = np.asarray(call(base))
        res = {}
        for name, change in cases.items():
            kw = {**base, **change}
            h, m = cache()
            v = np.asarray(call(kw))
            h1, m1 = cache()
            again = np.asarray(call(kw))
            h2, m2 = cache()
            res[name] = dict(
                first=[h1 - h, m1 - m], repeat=[h2 - h1, m2 - m1],
                own=bool(np.array_equal(v, np.asarray(fresh(**kw)))),
                same_on_repeat=bool(np.array_equal(v, again)),
                base_reused=bool(np.array_equal(np.asarray(call(base)), v0)),
            )
        print("RESULT", json.dumps(res))
        """,
        n_devices=4,
    )
    (line,) = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
@pytest.mark.parametrize("case", PROGRAM_CASES)
def test_changed_static_argument_builds_its_own_program(program_cases, case):
    got = program_cases[case]
    assert got["first"] == [0, 1]  # a miss, no hit
    assert got["repeat"] == [1, 0]  # then reused
    assert got["own"]  # bit-identical to this configuration's fresh program
    assert got["same_on_repeat"]
    assert got["base_reused"]  # the base call still gets its own answer
