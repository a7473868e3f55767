"""Invariance + parity properties of the aggregation ``backend=`` switch.

1. O(r) invariance: right-multiplying each machine's local basis by an
   arbitrary orthogonal matrix (rotation OR reflection) must not change what
   ``procrustes_fix_average`` estimates — elementwise when the reference is
   held fixed, as a subspace when the reference defaults to ``vs[0]`` (the
   reference rotates with machine 0, so the output basis does too).
   This is exactly the failure mode naive averaging has (paper Fig. 1), and
   it must hold under both backends.

2. Backend parity: ``backend="pallas"`` (kernels in interpret mode on CPU)
   must match ``backend="xla"`` within 1e-5 through the public API,
   including on ragged, non-MXU-aligned shapes.

3. Dispatch-cube parity: every (backend x polar x orth) cell of the
   dispatch cube — {xla, pallas} x {svd, newton-schulz} x
   {qr, cholesky-qr2} — computes the same estimator as the
   (xla, svd, qr) reference cell, to <= 1e-5 f64 subspace distance,
   including on a near-rank-deficient aligned average where the
   CholeskyQR2 conditioning guard is live.  The
   (pallas, newton-schulz, cholesky-qr2) cell is the fused one-launch
   path.

4. Wire-precision parity (PR 6): the collective at every (topology x
   comm_bits) cell agrees with the serial fp32 oracle within the
   bit-keyed ``repro.comm.PARITY_TOL`` — exactly 1e-5 at 32 bits (the
   wire is exact, so the historical cube tolerance is unchanged), and
   the documented looser bounds at 16/8 where the wire itself rounds
   (error feedback on; noisy-copies-of-a-common-subspace stacks, the
   paper's setting).  m=1 in-process, m=8 in a subprocess ring lane.

Parametrized over seeds rather than hypothesis so the property sweep runs
even without the 'test' extra installed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices, subspace_dist64

from repro.comm import PARITY_TOL
from repro.core import dist_2, iterative_refinement, procrustes_fix_average
from repro.data.synthetic import random_orthogonal

BACKENDS = ["xla", "pallas"]
POLARS = ["svd", "newton-schulz"]
ORTHS = ["qr", "cholesky-qr2"]

# deliberately ragged: d not a multiple of 8, r < 8, and an m == 1 case;
# d = 2100 > the kernels' default 2048 block exercises the pad path through
# the public API.
SHAPES = [(3, 205, 5), (1, 130, 3), (6, 96, 4), (2, 2100, 5)]


def _orthonormal_stack(seed, m, d, r):
    key = jax.random.PRNGKey(seed)
    vs = jnp.linalg.qr(jax.random.normal(key, (m, d, r)))[0]
    return vs


def _random_o_r(seed, m, r):
    """m random O(r) elements, half of them forced to be reflections."""
    qs = jnp.stack(
        [random_orthogonal(jax.random.PRNGKey(seed + i), r) for i in range(m)]
    )
    flip = jnp.where((jnp.arange(m) % 2 == 0)[:, None], -1.0, 1.0)
    return qs.at[:, :, 0].multiply(flip)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fixed_ref_elementwise_invariance(backend, seed):
    """With an external reference, aligned averaging is a function of the
    column spans only: V_i -> V_i Q_i leaves the output unchanged."""
    m, d, r = 4, 77, 5
    vs = _orthonormal_stack(seed, m, d, r)
    ref = _orthonormal_stack(seed + 100, 1, d, r)[0]
    qs = _random_o_r(seed * 7 + 1, m, r)
    rotated = jnp.einsum("mdr,mrs->mds", vs, qs)
    a = procrustes_fix_average(vs, ref, backend=backend)
    b = procrustes_fix_average(rotated, ref, backend=backend)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_default_ref_subspace_invariance(backend, seed):
    """With the paper's default reference (vs[0]), the estimated SUBSPACE is
    invariant to per-machine O(r) rotations/reflections.

    Local bases are noisy copies of one true subspace (the paper's setting):
    with mutually independent random bases the aligned average is
    near-singular and f32 QR roundoff swamps the invariance being tested.
    """
    m, d, r = 5, 64, 4
    u = _orthonormal_stack(seed + 50, 1, d, r)[0]
    noise = 0.1 * jax.random.normal(jax.random.PRNGKey(seed), (m, d, r))
    vs = jnp.linalg.qr(u[None] + noise)[0]
    qs = _random_o_r(seed * 13 + 3, m, r)
    rotated = jnp.einsum("mdr,mrs->mds", vs, qs)
    a = procrustes_fix_average(vs, backend=backend)
    b = procrustes_fix_average(rotated, backend=backend)
    # dist_2 bottoms out at ~sqrt(f32 eps) ~= 3.5e-4 (sin from cosines that
    # round to 1), so "equal to machine precision" is anything below ~1e-3.
    assert float(dist_2(a, b)) < 1e-3


@pytest.mark.parametrize("m,d,r", SHAPES)
def test_backend_parity_ragged(m, d, r):
    """Acceptance: pallas == xla within 1e-5 through the public API on
    ragged shapes (interpret mode on CPU)."""
    vs = _orthonormal_stack(42, m, d, r)
    a = procrustes_fix_average(vs, backend="xla")
    b = procrustes_fix_average(vs, backend="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_backend_parity_iterative_refinement():
    vs = _orthonormal_stack(7, 3, 205, 5)
    a = iterative_refinement(vs, n_iter=3, backend="xla")
    b = iterative_refinement(vs, n_iter=3, backend="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("polar", ["svd", "newton-schulz"])
@pytest.mark.parametrize("m,d,r", [(3, 205, 5), (2, 2100, 5)])
def test_backend_polar_matrix_parity(backend, polar, m, d, r):
    """Every (backend, polar) cell matches the (xla, svd) reference on
    ragged shapes through the public API."""
    vs = _orthonormal_stack(42, m, d, r)
    a = procrustes_fix_average(vs, backend="xla", polar="svd")
    b = procrustes_fix_average(vs, backend=backend, polar=polar)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def _weak_direction_stack(seed, m, d, r, eps=0.05):
    """Local solutions agreeing on r-1 strong directions plus one *weak*
    common direction of norm ~eps (deliberately non-orthonormal, as from an
    unnormalized sketch): the aligned average has kappa(V̄) ~ 1/eps = 20,
    where one CholeskyQR pass already loses ~eps_f32 * kappa^2 ~ 5e-5 of
    orthogonality — the second pass and the conditioning rule are live.
    The Grams stay well-conditioned (every machine sees the same weak
    direction), so the polar methods still agree."""
    key = jax.random.PRNGKey(seed)
    q = jnp.linalg.qr(jax.random.normal(key, (d, r)))[0]
    noise = 0.01 * jax.random.normal(jax.random.PRNGKey(seed + 1), (m, d, r))
    scale = jnp.concatenate(
        [jnp.ones((r - 1,)), jnp.asarray([eps])]
    )
    return (q[None] + noise) * scale


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("polar", POLARS)
@pytest.mark.parametrize("orth", ORTHS)
@pytest.mark.parametrize(
    "stack", ["ragged", "padded", "near-deficient"],
)
def test_backend_polar_orth_cube_parity(backend, polar, orth, stack):
    """Acceptance: the full dispatch cube agrees with the (xla, svd, qr)
    reference to <= 1e-5 f64 subspace distance — on ragged shapes, the
    d > 2048 pad path, and a near-rank-deficient aligned average."""
    vs = {
        "ragged": _orthonormal_stack(42, 3, 205, 5),
        "padded": _orthonormal_stack(43, 2, 2100, 5),
        "near-deficient": _weak_direction_stack(44, 8, 160, 4),
    }[stack]
    a = procrustes_fix_average(vs, backend="xla", polar="svd", orth="qr")
    b = procrustes_fix_average(vs, backend=backend, polar=polar, orth=orth)
    assert subspace_dist64(a, b) <= 1e-5, (backend, polar, orth, stack)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("orth", ORTHS)
def test_polar_parity_iterative_refinement(backend, orth):
    """orth="qr" cells agree elementwise (same orthonormalization, so the
    same in-span representative); "cholesky-qr2" picks a different (sign /
    rotation) representative of the same subspace, so parity is asserted
    on the span."""
    vs = _orthonormal_stack(11, 4, 130, 4)
    a = iterative_refinement(vs, n_iter=3, backend="xla", polar="svd")
    b = iterative_refinement(
        vs, n_iter=3, backend=backend, polar="newton-schulz", orth=orth
    )
    if orth == "qr":
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    else:
        assert subspace_dist64(a, b) <= 1e-5


def test_polar_invalid_raises():
    vs = _orthonormal_stack(0, 2, 16, 2)
    with pytest.raises(ValueError):
        procrustes_fix_average(vs, polar="cholesky")


def test_orth_invalid_raises():
    vs = _orthonormal_stack(0, 2, 16, 2)
    with pytest.raises(ValueError):
        procrustes_fix_average(vs, orth="householder")


def _noisy_copy_stack(seed, m, d, r, noise=0.1):
    """Noisy copies of one true subspace — the paper's setting, and the
    regime PARITY_TOL was calibrated on."""
    u = _orthonormal_stack(seed + 50, 1, d, r)[0]
    eps = noise * jax.random.normal(jax.random.PRNGKey(seed), (m, d, r))
    return jnp.linalg.qr(u[None] + eps)[0]


@pytest.mark.parametrize("comm_bits", [32, 16, 8])
@pytest.mark.parametrize("topology", ["psum", "gather", "ring"])
def test_comm_bits_parity_single_device(topology, comm_bits):
    """Fast lane of the bit-keyed parity cube: on a 1-device mesh every
    (topology, comm_bits) cell stays within PARITY_TOL[bits] of the
    serial fp32 oracle.  At 32 the wire is exact (1e-5, the historical
    cube bound); the lossy tiers round the broadcast payload even at
    m=1, so they get their documented looser bounds."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from repro.launch.mesh import make_mesh
    from repro.core import refinement_rounds
    from repro.core.distributed import procrustes_average_collective

    d, r = 96, 4
    vs = _noisy_copy_stack(3, 1, d, r)
    ser = refinement_rounds(vs, n_iter=2)
    mesh = make_mesh((1,), ("data",))
    fn = jax.jit(shard_map(
        lambda v: procrustes_average_collective(
            v[0], axis_name="data", n_iter=2, topology=topology,
            comm_bits=comm_bits,
        )[None],
        mesh=mesh, in_specs=P("data", None, None),
        out_specs=P("data", None, None), check_vma=False,
    ))
    got = fn(vs)[0]
    assert subspace_dist64(ser, got) <= PARITY_TOL[comm_bits], (
        topology, comm_bits,
    )


@pytest.mark.slow
def test_comm_bits_parity_cube_eight_devices():
    """Acceptance: the full (topology x comm_bits) parity cube at m=8 on
    noisy-copy stacks — every cell within PARITY_TOL[bits] of the serial
    fp32 oracle, through the subprocess ring lane like the rest of the
    multi-device suite.  The 32-bit column must hold the exact-wire
    1e-5; 16/8 hold the documented calibrated bounds."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import refinement_rounds
        from repro.core.distributed import procrustes_average_collective
        from repro.core.metrics import subspace_dist64

        m, d, r = 8, 96, 4
        u = jnp.linalg.qr(
            jax.random.normal(jax.random.PRNGKey(53), (d, r)))[0]
        noise = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (m, d, r))
        vs = jnp.linalg.qr(u[None] + noise)[0]
        ser = refinement_rounds(vs, n_iter=2)
        mesh = make_mesh((m,), ("data",))
        for topo in ("psum", "gather", "ring"):
            for cb in (32, 16, 8):
                fn = jax.jit(shard_map(
                    lambda v, t=topo, b=cb: procrustes_average_collective(
                        v[0], axis_name="data", n_iter=2, topology=t,
                        comm_bits=b)[None],
                    mesh=mesh, in_specs=P("data", None, None),
                    out_specs=P("data", None, None), check_vma=False,
                ))
                got = fn(vs)[0]
                print("CELL", topo, cb, float(subspace_dist64(ser, got)))
        """
    )
    cells = [ln.split() for ln in out.strip().splitlines()
             if ln.startswith("CELL")]
    assert len(cells) == 9
    for _, topo, cb, dist in cells:
        assert float(dist) <= PARITY_TOL[int(cb)], (topo, cb, dist)


def test_auto_backend_resolves():
    from repro.kernels.ops import on_tpu, resolve_backend

    assert resolve_backend("auto") in ("xla", "pallas")
    if not on_tpu():
        assert resolve_backend("auto") == "xla"
    with pytest.raises(ValueError):
        resolve_backend("tpu")


def test_backend_invalid_raises():
    vs = _orthonormal_stack(0, 2, 16, 2)
    with pytest.raises(ValueError):
        procrustes_fix_average(vs, backend="mosaic")
