"""Behavioural tests for the paper's estimators (Algorithm 1/2 + baselines).

These assert the paper's *claims* at small scale:
  - Alg 1 tracks the centralized estimator (Theorem 3),
  - naive averaging fails under adversarial rotations (Section 1 / Fig 1),
  - Alg 2 helps when n is small (Section 3.2),
  - the deterministic bound of Theorem 1 holds numerically,
  - r = 1 recovers the sign-fixing behaviour.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    central_estimate,
    dist_2,
    empirical_covariance,
    eigengap,
    intdim,
    iterative_refinement,
    local_bases,
    naive_average,
    procrustes_fix_average,
    projector_average,
    subspace_iteration,
    top_r_eigh,
)
from repro.data import synthetic as syn


def _make_problem(key, d=100, r=4, m=10, n=300, delta=0.2, model="m1", r_star=None):
    if model == "m1":
        tau = syn.spectrum_m1(d, r, delta=delta)
    else:
        tau = syn.spectrum_m2(d, r, r_star or (r + 16), delta=delta)
    k1, k2 = jax.random.split(key)
    sigma, u, factor = syn.covariance_from_spectrum(k1, tau)
    v1 = u[:, :r]
    keys = jax.random.split(k2, m)
    xs = jnp.stack([syn.sample_gaussian(k, factor, n) for k in keys])
    covs = jax.vmap(lambda x: empirical_covariance(x))(xs)
    return sigma, v1, covs


def test_alg1_matches_central():
    key = jax.random.PRNGKey(0)
    sigma, v1, covs = _make_problem(key, d=100, r=4, m=10, n=300)
    vs = local_bases(covs, 4)
    err_alg1 = float(dist_2(procrustes_fix_average(vs), v1))
    err_cent = float(dist_2(central_estimate(covs, 4)[0], v1))
    err_local = float(dist_2(vs[0], v1))
    # Alg 1 must be within a small constant of central and beat any local sol.
    assert err_alg1 < 3.0 * err_cent + 0.02
    assert err_alg1 < 0.7 * err_local


def test_naive_average_fails_under_rotation():
    """Rotate each local basis by a random orthogonal factor (which is a
    no-op for the subspace) — naive averaging must degrade, Alg 1 must not."""
    key = jax.random.PRNGKey(1)
    sigma, v1, covs = _make_problem(key, d=80, r=4, m=16, n=400)
    vs = local_bases(covs, 4)
    zs = jnp.stack(
        [syn.random_orthogonal(jax.random.PRNGKey(100 + i), 4) for i in range(16)]
    )
    vs_rot = jnp.einsum("mdr,mrs->mds", vs, zs)
    err_naive = float(dist_2(naive_average(vs_rot), v1))
    err_alg1 = float(dist_2(procrustes_fix_average(vs_rot), v1))
    assert err_naive > 0.5, f"naive unexpectedly good: {err_naive}"
    assert err_alg1 < 0.2, f"alg1 unexpectedly bad: {err_alg1}"


def test_alg1_invariant_to_local_rotations():
    key = jax.random.PRNGKey(2)
    _, v1, covs = _make_problem(key, d=60, r=3, m=8, n=300)
    vs = local_bases(covs, 3)
    zs = jnp.stack(
        [syn.random_orthogonal(jax.random.PRNGKey(200 + i), 3) for i in range(8)]
    )
    # Rotate every machine EXCEPT the reference (so ref is identical).
    zs = zs.at[0].set(jnp.eye(3))
    vs_rot = jnp.einsum("mdr,mrs->mds", vs, zs)
    a = procrustes_fix_average(vs)
    b = procrustes_fix_average(vs_rot)
    assert float(dist_2(a, b)) < 1e-3


def test_alg2_refinement_helps_small_n():
    """With few samples per machine the reference is poor; refinement should
    (weakly) improve the estimate, per Section 3.2."""
    errs1, errs2 = [], []
    for seed in range(5):
        key = jax.random.PRNGKey(40 + seed)
        _, v1, covs = _make_problem(key, d=80, r=4, m=24, n=60, model="m2", r_star=24)
        vs = local_bases(covs, 4)
        errs1.append(float(dist_2(procrustes_fix_average(vs), v1)))
        errs2.append(float(dist_2(iterative_refinement(vs, n_iter=5), v1)))
    assert np.median(errs2) <= np.median(errs1) + 0.01


def test_projector_average_baseline_comparable():
    key = jax.random.PRNGKey(3)
    _, v1, covs = _make_problem(key, d=80, r=4, m=10, n=300)
    vs = local_bases(covs, 4)
    err_proj = float(dist_2(projector_average(vs, 4), v1))
    err_alg1 = float(dist_2(procrustes_fix_average(vs), v1))
    # Within a modest constant of each other (paper Fig. 5).
    assert err_alg1 < 2.5 * err_proj + 0.02
    assert err_proj < 2.5 * err_alg1 + 0.02


def test_deterministic_bound_theorem1():
    """dist_2(V~, V1) <= C * (max_i ||E_i||^2 / delta^2 + ||mean E|| / delta)."""
    key = jax.random.PRNGKey(4)
    d, r, m, n = 80, 4, 8, 500
    sigma, v1, covs = _make_problem(key, d=d, r=r, m=m, n=n)
    delta = 0.2
    errs = jnp.linalg.norm(covs - sigma[None], ord=2, axis=(1, 2))
    mean_err = float(jnp.linalg.norm(jnp.mean(covs, axis=0) - sigma, ord=2))
    bound = float(jnp.max(errs) ** 2) / delta**2 + mean_err / delta
    vs = local_bases(covs, r)
    err = float(dist_2(procrustes_fix_average(vs), v1))
    # Theorem 1 is up to an absolute constant; C=10 is a generous numeric check
    assert err <= 10.0 * bound


def test_error_decreases_with_more_machines():
    """Thm 3: error ~ sqrt(1/(mn)) + 1/n — at fixed n, more machines help."""
    errs = {}
    for m in (2, 16):
        vals = []
        for seed in range(4):
            key = jax.random.PRNGKey(500 + seed)
            _, v1, covs = _make_problem(key, d=60, r=3, m=m, n=150)
            vs = local_bases(covs, 3)
            vals.append(float(dist_2(procrustes_fix_average(vs), v1)))
        errs[m] = np.median(vals)
    assert errs[16] < errs[2]


def test_subspace_iteration_agrees_with_eigh():
    key = jax.random.PRNGKey(5)
    tau = syn.spectrum_m1(64, 4, delta=0.2)
    sigma, u, _ = syn.covariance_from_spectrum(key, tau)
    v_e, lam_e = top_r_eigh(sigma, 4)
    v_s, lam_s = subspace_iteration(sigma, 4, iters=60, key=jax.random.PRNGKey(6))
    assert float(dist_2(v_e, v_s)) < 1e-3
    np.testing.assert_allclose(np.asarray(lam_s), np.asarray(lam_e), rtol=1e-3)


@pytest.mark.parametrize("k1", [0, 4, 13])
def test_subspace_pieces_compose_to_subspace_iteration(k1):
    """start, two runs of steps (k1 + k2 = iters) and finish, each its own
    program as the slices of a sliced refresh are, give
    ``subspace_iteration``'s basis and Ritz values bit for bit."""
    from repro.core.subspace import (
        subspace_finish,
        subspace_start,
        subspace_steps,
    )

    iters, r = 13, 4
    tau = syn.spectrum_m1(64, r, delta=0.2)
    sigma, _, _ = syn.covariance_from_spectrum(jax.random.PRNGKey(5), tau)
    start = jax.jit(subspace_start, static_argnums=1)
    steps = jax.jit(subspace_steps, static_argnums=2)
    q = steps(sigma, steps(sigma, start(sigma, r), k1), iters - k1)
    v, lam = jax.jit(subspace_finish)(sigma, q)
    want_v, want_lam = subspace_iteration(sigma, r, iters=iters)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(lam), np.asarray(want_lam))


def test_intdim_and_eigengap():
    tau = syn.spectrum_m2(128, 4, 24.0, delta=0.25)
    sigma, _, _ = syn.covariance_from_spectrum(jax.random.PRNGKey(7), tau)
    rd = float(intdim(sigma))
    assert 0.5 * 24 < rd < 1.5 * 24
    assert abs(float(eigengap(tau, 4)) - 0.25) < 1e-5


def test_dk_distribution_second_moment():
    """D_k atoms have squared norm d, so E[xx^T] has trace d."""
    atoms = syn.make_dk_atoms(jax.random.PRNGKey(8), 32, 8)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(atoms), axis=1) ** 2, 32.0, rtol=1e-5
    )
