"""Genotype PCA through ``distributed_pca``: int8 counts standardized per
site by Patterson et al. (2006) eq. 3, solved in the row form
(``repro.core.rowop``), against a plain reference.

The reference below is straightforward ``jax.numpy`` in float32 at
HIGHEST: it standardizes each site's counts explicitly, one block of rows
at a time, runs subspace iteration on Xᵀ(XQ)/n from the program's start
key (PRNGKey(0)), and aggregates the sites' bases by Algorithm 2 in
float64 numpy.  Sizes: 96 rows per site, d=300, r=5, K=6 populations.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices, subspace_dist64

from repro.core.distributed import (
    _LocalStage,
    _local_form,
    _mesh_program,
    distributed_pca,
    procrustes_average_collective,
)
from repro.core.rowop import RowOperator
from repro.core.subspace import local_eigenbasis, subspace_iteration
from repro.data import genotypes as gt
from repro.launch.mesh import make_mesh

N, D, R, K = 96, 300, 5, 6
ITERS, N_ITER = 30, 2
# F_ST=0.1 (ten times the UK Biobank configuration's) puts the five
# ancestry eigenvalues clear of the noise at this small d.
FST, ALPHA = 0.1, 0.1


def _sites(m, seed=0):
    """(m·N, D) int8 counts: m sites drawn from one set of populations."""
    kf, kc = jax.random.split(jax.random.PRNGKey(seed))
    freqs = gt.population_frequencies(kf, D, K, fst=FST)
    return jnp.concatenate([
        gt.genotype_rows(k, freqs, N, alpha=ALPHA)
        for k in jax.random.split(kc, m)])


def _plain_site_basis(c, r, iters, block=32):
    """One site's span: Patterson eq. 3 and subspace iteration on
    Xᵀ(XQ)/n, written out, in row blocks, float32 at HIGHEST."""
    n, d = c.shape
    with jax.default_matmul_precision("highest"):
        s = sum(jnp.sum(c[i:i + block].astype(jnp.int32), axis=0)
                for i in range(0, n, block)).astype(jnp.float32)
        mu = s / n
        p = (1.0 + s) / (2.0 + 2.0 * n)
        sd = jnp.sqrt(p * (1.0 - p))
        q, _ = jnp.linalg.qr(
            jax.random.normal(jax.random.PRNGKey(0), (d, r), jnp.float32))
        for _ in range(iters):
            w = jnp.zeros((d, r), jnp.float32)
            for i in range(0, n, block):
                x = (c[i:i + block].astype(jnp.float32) - mu) / sd
                w = w + x.T @ (x @ q)
            q, _ = jnp.linalg.qr(w / n)
    return np.asarray(q, np.float64)


def _algorithm2(bases, n_iter):
    """Procrustes-fixed averaging against the first site's basis, float64."""
    def polar(a):
        u, _, vt = np.linalg.svd(a)
        return u @ vt

    ref = bases[0]
    for _ in range(n_iter):
        vbar = sum(v @ polar(v.T @ ref) for v in bases) / len(bases)
        ref, _ = np.linalg.qr(vbar)
    return ref


def plain_reference(counts, m):
    sites = np.split(np.asarray(counts), m)
    return _algorithm2([_plain_site_basis(jnp.asarray(c), R, ITERS)
                        for c in sites], N_ITER)


def test_patterson_eq3_and_monomorphic_markers():
    c = np.array(_sites(1))
    c[:, 0] = 0  # monomorphic: nobody carries the allele
    c[:, 1] = 2  # monomorphic: everybody carries two copies
    x = np.asarray(gt.patterson(jnp.asarray(c)), np.float64)
    n = c.shape[0]
    mu = c.sum(0) / n
    p = (1 + c.sum(0)) / (2 + 2 * n)
    want = (c - mu) / np.sqrt(p * (1 - p))
    np.testing.assert_allclose(x, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(x[:, :2], 0.0)


# A device too small for the (d, d) float32 covariance at this test's
# width, as a v5e's 16 GB is at UK Biobank's d=147,604 (87 GB).
SMALL_DEVICE = 4 * D * D - 1


@pytest.fixture
def small_device(monkeypatch):
    import repro.core.distributed as distributed

    monkeypatch.setattr(distributed, "_device_bytes", lambda dev: SMALL_DEVICE)


# (d, device bytes, form)
FORMS = [
    (8192, 16e9, "cov"),             # paper_pca: 268 MB
    (4096, 16e9, "cov"),             # wider than 2,048 rows: still fits
    (147604, 16e9, "rows"),          # ukb_genotype_pca: 87 GB
    (70000, 16e9, "rows"),           # 19.6 GB
    (147604, None, "cov"),           # a device that reports no memory
    (D, SMALL_DEVICE, "rows"),
]


@pytest.mark.parametrize("d,device_bytes,form", FORMS)
def test_the_form_follows_the_shapes(d, device_bytes, form):
    assert _local_form(d, device_bytes) == form


def test_eigh_with_the_row_form_raises(small_device):
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="solver='subspace'"):
        distributed_pca(_sites(1), mesh, R, standardize="patterson",
                        solver="eigh")


def test_unknown_standardization_raises():
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="standardization"):
        distributed_pca(_sites(1), mesh, R, standardize="global",
                        solver="subspace")


def _gaussian_rows():
    return jax.random.normal(jax.random.PRNGKey(3), (N, D)) * jnp.linspace(
        3.0, 0.5, D)


# Both forms run the same iteration from the same start on the same
# float32 rows; they differ in rounding only (XᵀX formed once against
# Xᵀ(XQ) every step), which moved the span by 2.3e-7 (genotypes) and
# 1.5e-6 (Gaussian rows, λ₆/λ₅ = 0.97) here.
@pytest.mark.parametrize("rows", ["patterson", "gaussian"])
def test_the_row_form_and_the_covariance_form_agree(rows):
    x = gt.patterson(_sites(1)) if rows == "patterson" else _gaussian_rows()
    v_rows, lam_rows = subspace_iteration(RowOperator(x), R, iters=ITERS)
    with jax.default_matmul_precision("highest"):
        cov = x.T @ x / x.shape[0]
    v_cov, lam_cov = subspace_iteration(cov, R, iters=ITERS)
    assert subspace_dist64(v_rows, v_cov) < 1e-5
    np.testing.assert_allclose(lam_rows, lam_cov, rtol=1e-5)


# On a device too small for the (d, d) covariance the row form runs; on
# one that reports no memory (the CPU), the covariance form of the rows
# standardized in full.
@pytest.mark.parametrize("device_bytes,form",
                         [(SMALL_DEVICE, "rows"), (None, "cov")])
def test_one_device_matches_the_plain_reference(monkeypatch, device_bytes,
                                                form):
    import repro.core.distributed as distributed

    monkeypatch.setattr(distributed, "_device_bytes", lambda dev: device_bytes)
    counts = _sites(1)
    mesh = make_mesh((1,), ("data",))
    assert distributed._shard_form(counts, mesh) == form
    v = distributed_pca(counts, mesh, R, standardize="patterson",
                        solver="subspace", iters=ITERS, n_iter=N_ITER)
    # float32 rounding of two differently ordered computations of one
    # iteration (blocked and unblocked sums): 2.6e-7 for the row form.
    assert subspace_dist64(v, plain_reference(counts, 1)) < 1e-5


@pytest.mark.slow
def test_four_devices_match_the_plain_reference():
    out = run_with_devices(
        f"""
        import json
        import jax, jax.numpy as jnp, numpy as np
        import repro.core.distributed as distributed
        from repro.core.distributed import distributed_pca
        from repro.data import genotypes as gt
        from repro.launch.mesh import make_mesh

        # Too small for the (d, d) covariance: the row form.
        distributed._device_bytes = lambda dev: {SMALL_DEVICE}
        kf, kc = jax.random.split(jax.random.PRNGKey(0))
        freqs = gt.population_frequencies(kf, {D}, {K}, fst={FST})
        counts = jnp.concatenate([
            gt.genotype_rows(k, freqs, {N}, alpha={ALPHA})
            for k in jax.random.split(kc, 4)])
        mesh = make_mesh((4,), ("data",))
        v = distributed_pca(counts, mesh, {R}, standardize="patterson",
                            solver="subspace", iters={ITERS},
                            n_iter={N_ITER})
        print("RESULT", json.dumps(np.asarray(v).tolist()))
        """,
        n_devices=4,
    )
    v = np.array(json.loads(out.split("RESULT", 1)[1]))
    # Each site standardizes with its own frequencies: the sites differ,
    # and the reference sees the same four sites.
    want = plain_reference(_sites(4), 4)
    assert subspace_dist64(v, want) < 1e-5
    # Standardizing all four sites as one would be another estimator.
    pooled = _algorithm2([_plain_site_basis(_sites(4), R, ITERS)], 1)
    assert subspace_dist64(pooled, want) > 1e-3


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_the_row_form_program_holds_no_d_by_d_array(small_device):
    counts = jax.ShapeDtypeStruct((N, D), jnp.int8)
    mesh = make_mesh((1,), ("data",))
    text = _hlo(lambda c: distributed_pca(
        c, mesh, R, standardize="patterson", solver="subspace",
        n_iter=N_ITER), counts)
    assert f"[{D},{D}]" not in text
    assert f"s8[{N},{D}]" in text


def _strip_metadata(text):
    """The computations of an optimized HLO module, without the module's
    name, its table of source locations, and each op's metadata."""
    import re

    body = text[text.index("\n%"):]
    return re.sub(r",? metadata=\{[^}]*\}", "", body)


@pytest.mark.parametrize("solver", ["eigh", "subspace"])
def test_the_covariance_form_program_is_unchanged(solver):
    """The covariance form's program is the composition it was before the
    row form existed: covariance, local solve, Algorithm 2."""
    from repro.comm.membership import resolve_membership
    from repro.core.covariance import empirical_covariance
    from repro.plan.planner import resolve_plan
    from jax.sharding import PartitionSpec as P

    d, n, r = 64, 256, 4
    mesh = make_mesh((1,), ("data",))
    x = jax.ShapeDtypeStruct((n, d), jnp.float32)
    mem = resolve_membership(None, 1)
    pl = resolve_plan(None, m=1, d=d, r=r, n_iter=N_ITER, membership=mem)
    assert _local_form(d, None) == "cov"
    local = _LocalStage("rows", r, solver, ITERS, backend=pl.backend)
    new = _mesh_program.__wrapped__(mesh, "data", False, local,
                                    (N_ITER, pl, mem), 2)

    def shard_fn(x_shard):
        v, _ = local_eigenbasis(empirical_covariance(x_shard,
                                                     backend=pl.backend),
                                r, method=solver, iters=ITERS)
        return procrustes_average_collective(
            v, axis_name="data", n_iter=N_ITER, plan=pl,
            membership=mem)[None]

    old = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(P("data", None),),
        out_specs=P("data", None, None), check_vma=False))
    assert (_strip_metadata(new.lower(x).compile().as_text())
            == _strip_metadata(old.lower(x).compile().as_text()))
