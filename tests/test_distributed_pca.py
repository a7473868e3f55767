"""Distributed (shard_map) runtime == serial reference, on real fake meshes.

Multi-device cases run in a subprocess with
``--xla_force_host_platform_device_count`` so the main test process keeps a
single device (per the project rules).
"""

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
