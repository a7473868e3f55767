"""Property coverage for ``sign_average_collective`` and the
``naive_average`` collapse (paper Fig. 1), across the ``orth=`` switch.

The collapse property is the paper's motivation: adversarially rotated
local bases destroy the naive average (the mean cancels before
orthonormalization, under *any* ``orth`` method) but not the
Procrustes-fixed paths, which undo the rotations first.  The rank-1
analogue is sign flips vs. ``sign_average_collective``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices, subspace_dist64

from repro.core import dist_2, naive_average, procrustes_fix_average
from repro.data.synthetic import random_orthogonal

ORTHS = ["qr", "cholesky-qr2"]


def _noisy_copies(seed, m, d, r, noise=0.02):
    """m orthonormal bases estimating one true subspace; returns (vs, u)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    u = jnp.linalg.qr(jax.random.normal(k1, (d, r)))[0]
    vs = jnp.linalg.qr(
        u[None] + noise * jax.random.normal(k2, (m, d, r))
    )[0]
    return vs, u


def _adversarial_rotations(seed, m, r):
    """O(r) elements cancelling in pairs (Q_{2k+1} = -Q_{2k}, m even), so
    the raw mean of rotated copies collapses toward zero."""
    assert m % 2 == 0
    qs = jnp.stack(
        [random_orthogonal(jax.random.PRNGKey(seed + i), r) for i in range(m // 2)]
    )
    return jnp.concatenate([qs, -qs]).reshape(2, m // 2, r, r).swapaxes(
        0, 1
    ).reshape(m, r, r)


@pytest.mark.parametrize("orth", ORTHS)
def test_naive_collapses_procrustes_does_not(orth):
    m, d, r = 4, 96, 3
    vs, u = _noisy_copies(0, m, d, r)
    qs = _adversarial_rotations(7, m, r)
    rotated = jnp.einsum("mdr,mrs->mds", vs, qs)
    err_naive = float(dist_2(naive_average(rotated, orth=orth), u))
    assert err_naive > 0.5, "adversarial rotations should destroy naive avg"
    for backend in ("xla", "pallas"):
        fixed = procrustes_fix_average(
            rotated, vs[0],
            backend=backend,
            polar="newton-schulz" if orth == "cholesky-qr2" else "svd",
            orth=orth,
        )
        err_fixed = float(dist_2(fixed, u))
        assert err_fixed < 0.2, (backend, orth, err_fixed)
        assert err_fixed < err_naive / 3


@pytest.mark.parametrize("orth", ORTHS)
def test_naive_collapse_is_orth_independent(orth):
    """The collapse happens in the mean, before orthonormalization: the
    collapsed average is near-rank-deficient, and the guarded CholeskyQR2
    must survive it (finite, no NaN) exactly like Householder QR."""
    m, d, r = 4, 120, 4
    vs, _ = _noisy_copies(3, m, d, r, noise=1e-3)
    flipped = vs * jnp.where(
        (jnp.arange(m) % 2 == 0)[:, None, None], 1.0, -1.0
    )
    vbar = jnp.mean(flipped, axis=0)
    assert float(jnp.linalg.norm(vbar)) < 0.1  # genuinely collapsed
    out = naive_average(flipped, orth=orth)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert out.shape == (d, r)


def test_naive_average_orth_methods_agree_when_well_conditioned():
    vs, _ = _noisy_copies(5, 5, 130, 4)
    a = naive_average(vs, orth="qr")
    b = naive_average(vs, orth="cholesky-qr2")
    assert subspace_dist64(a, b) <= 1e-5


@pytest.mark.slow
def test_sign_average_collective_eight_devices():
    """Rank-1 collective: sign flips destroy the naive psum mean but not
    ``sign_average_collective``; the collective matches the serial
    ``sign_fix`` average."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import sign_average_collective
        from repro.core import procrustes

        m, d = 8, 64
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        u = jax.random.normal(k1, (d,))
        u = u / jnp.linalg.norm(u)
        vs = u[None] + 0.05 * jax.random.normal(k2, (m, d))
        vs = vs / jnp.linalg.norm(vs, axis=1, keepdims=True)
        signs = jnp.where(jnp.arange(m) % 2 == 0, 1.0, -1.0)
        flipped = vs * signs[:, None]

        mesh = make_mesh((m,), ("data",))
        fn = jax.jit(shard_map(
            lambda v: sign_average_collective(v[0], axis_name="data")[None],
            mesh=mesh, in_specs=P("data", None),
            out_specs=P("data", None), check_vma=False,
        ))
        got = fn(flipped)[0]

        fixed = jnp.stack([procrustes.sign_fix(v, flipped[0]) for v in flipped])
        vbar = jnp.mean(fixed, axis=0)
        ser = vbar / jnp.linalg.norm(vbar)
        print("PAR", float(jnp.abs(got - ser).max()))
        print("ALIGN", float(jnp.abs(jnp.dot(got, u))))
        naive = jnp.mean(flipped, axis=0)
        print("NAIVENORM", float(jnp.linalg.norm(naive)))
        """
    )
    vals = {
        line.split()[0]: float(line.split()[1])
        for line in out.strip().splitlines()
        if line and line.split()[0] in ("PAR", "ALIGN", "NAIVENORM")
    }
    assert vals["PAR"] < 1e-5          # collective == serial sign-fix avg
    assert vals["ALIGN"] > 0.95        # recovers the true direction
    assert vals["NAIVENORM"] < 0.3     # the naive mean really collapsed


@pytest.mark.slow
def test_collective_orth_switch_eight_devices():
    """``orth=`` threads through the psum and all-gather topologies: all
    four (backend, orth) collective cells match the serial reference."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import procrustes_fix_average
        from repro.core.distributed import procrustes_average_collective

        m, d, r = 8, 96, 4
        vs = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(0), (m, d, r)))[0]
        ser = procrustes_fix_average(vs)
        mesh = make_mesh((m,), ("data",))
        for backend in ("xla", "pallas"):
            for orth in ("qr", "cholesky-qr2"):
                fn = jax.jit(shard_map(
                    lambda v, b=backend, o=orth: procrustes_average_collective(
                        v[0], axis_name="data", backend=b, orth=o)[None],
                    mesh=mesh, in_specs=P("data", None, None),
                    out_specs=P("data", None, None), check_vma=False,
                ))
                got = fn(vs)[0]
                import numpy as np
                a = np.asarray(ser, np.float64); b_ = np.asarray(got, np.float64)
                a, _ = np.linalg.qr(a); b_, _ = np.linalg.qr(b_)
                c = np.clip(np.linalg.svd(a.T @ b_, compute_uv=False), 0, 1)
                print("CELL", backend, orth,
                      float(np.sqrt(max(1 - c.min() ** 2, 0))))
        """
    )
    cells = [line.split() for line in out.strip().splitlines()
             if line.startswith("CELL")]
    assert len(cells) == 4
    for _, backend, orth, dist in cells:
        assert float(dist) <= 1e-5, (backend, orth, dist)
