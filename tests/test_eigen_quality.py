"""Quality guarantees for the eigen-compressed optimizer (role R2):
the paper's technique must not degrade training, and its alignment step
must make the combined basis invariant to per-shard rotations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices


def test_eigen_training_matches_full_adamw_quality():
    """Compressed-DP training must reach a loss comparable to full AdamW on
    the same stream (within 15% after warmup)."""
    from repro.launch.train import train

    common = dict(steps=30, batch=4, seq=32, lr=1e-3, reduced=True, log_every=1000)
    _, _, base = train("granite-3-2b", **common)
    _, _, eig = train(
        "granite-3-2b", eigen=True, eigen_rank=16, eigen_refresh=5, **common
    )
    b = float(np.mean(base[-5:]))
    e = float(np.mean(eig[-5:]))
    assert e < 1.15 * b + 0.05, (b, e)
    # and it actually trains (30 warmup-heavy steps: expect a clear decrease)
    assert e < float(np.mean(eig[:3])) - 0.05


@pytest.mark.slow
def test_refresh_basis_rotation_invariance():
    """The Procrustes-combined basis must span the same subspace no matter
    how each shard's local eigensolver rotated its output — the exact
    failure naive basis-averaging has (paper Fig. 1, applied to R2)."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import dist_2
        from repro.optim.eigen_compress import (EigenCompressConfig,
                                                refresh_basis, _local_basis)
        mesh = make_mesh((4,), ("data",))
        ecfg = EigenCompressConfig(rank=4, power_iters=8)
        d, n = 48, 32
        # shared low-rank signal + per-shard noise
        key = jax.random.PRNGKey(0)
        u, _ = jnp.linalg.qr(jax.random.normal(key, (d, 4)))
        gs = jnp.stack([
            u @ jax.random.normal(jax.random.PRNGKey(i), (4, n))
            + 0.05 * jax.random.normal(jax.random.PRNGKey(10 + i), (d, n))
            for i in range(4)
        ])
        def job(gs):
            def f(g):
                basis = refresh_basis(
                    g[0], jnp.zeros((d, 4)), jnp.zeros((), jnp.bool_),
                    axis_name="data", cfg=ecfg, key=jax.random.PRNGKey(42))
                return basis[None]
            return shard_map(f, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"), check_vma=False)(gs)
        b1 = job(gs)[0]
        print("DIST_TRUTH", float(dist_2(b1, u)))
        """,
        n_devices=4,
    )
    val = float(out.strip().splitlines()[-1].split()[1])
    assert val < 0.2


def test_error_feedback_plus_refresh_is_lossless_over_time():
    """Error feedback + periodic basis refresh (from the error-carrying
    gradient) must deliver the full gradient in the long run.  NOTE a fixed
    basis provably cannot: the orthogonal component accumulates in ``err``
    and is only drained because the refresh re-estimates the basis from
    g + err — exactly what eigen_refresh_step does every K steps."""
    from repro.optim.eigen_compress import _local_basis

    d, n, r = 32, 16, 4
    key = jax.random.PRNGKey(0)
    # realistic low-rank-dominant gradient (rank 3 signal + small noise);
    # a rank-r basis of a FULL-rank signal can only drain r dims per period
    u = jnp.linalg.qr(jax.random.normal(key, (d, 3)))[0]
    g = u @ jax.random.normal(jax.random.PRNGKey(9), (3, n)) + 0.02 * (
        jax.random.normal(jax.random.PRNGKey(8), (d, n))
    )
    basis = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(1), (d, r)))[0]
    err = jnp.zeros((d, n))
    delivered = jnp.zeros((d, n))
    steps, refresh = 60, 5
    for t in range(steps):
        if t % refresh == 0 and t > 0:
            basis = _local_basis(
                g + err, r, iters=6, key=jax.random.PRNGKey(100 + t)
            )
        g_eff = g + err
        g_hat = basis @ (basis.T @ g_eff)
        err = g_eff - g_hat
        delivered = delivered + g_hat
    np.testing.assert_allclose(
        np.asarray(delivered / steps), np.asarray(g), atol=0.2
    )
