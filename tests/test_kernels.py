"""Pallas kernel validation: shape/dtype sweeps vs. the ref.py oracles
(interpret mode on CPU), plus hypothesis property sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need the 'test' extra")
from hypothesis import given, settings, strategies as st

from repro.kernels import covariance, flash_attention, procrustes_align, ref
from repro.kernels import ops

TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-1}


# ---------------------------------------------------------------- gram ----
@pytest.mark.parametrize("n,d", [(64, 64), (300, 200), (257, 129), (8, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_shapes(n, d, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d), dtype=dtype)
    got = covariance.gram(x, bn=128, bd=128, interpret=True)
    want = ref.gram(x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=TOL[dtype] * d, rtol=1e-2
    )


@pytest.mark.parametrize("n,d", [(192, 256), (300, 520)])
def test_gram_block_size_invariance(n, d):
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    outs = [
        covariance.gram(x, bn=bn, bd=bd, interpret=True)
        for bn, bd in [(64, 64), (128, 128), (192, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(
            np.asarray(outs[0]), np.asarray(o), atol=1e-3
        )


@pytest.mark.parametrize("bn", [256, 1024])
def test_gram_sums_in_128_row_steps(bn):
    """A deeper row block sums 128 rows at a time into the output, so G is
    bit for bit the one 128-row blocks give."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1024, 384))
    want = np.asarray(covariance.gram(x, bn=128, bd=128, interpret=True))
    got = np.asarray(covariance.gram(x, bn=bn, bd=384, interpret=True))
    assert np.array_equal(got, want)


# Several tiles on each side, ragged in n and d, small forced blocks.
TRIANGLE_CASES = [(300, 1030, 64, 256), (1000, 2100, 128, 512)]


@pytest.mark.parametrize("n,d,bn,bd", TRIANGLE_CASES)
def test_gram_triangle_grid(n, d, bn, bd):
    x = jax.random.normal(jax.random.PRNGKey(2), (n, d))
    got = covariance.gram(x, bn=bn, bd=bd, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.gram(x)), atol=1e-5 * n, rtol=1e-5
    )


@pytest.mark.parametrize(
    "n,d,bn,bd", TRIANGLE_CASES + [(257, 129, None, None)]
)
def test_gram_exactly_symmetric(n, d, bn, bd):
    """The lower triangle is the computed upper one, selected bit for bit
    (the kernel never writes the lower tiles)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (n, d))
    g = np.asarray(covariance.gram(x, bn=bn, bd=bd, interpret=True))
    assert np.array_equal(g, g.T)
    assert np.isfinite(g).all()


@pytest.mark.parametrize(
    "n,d,itemsize",
    [(65536, 8192, 4), (65536, 8192, 2), (18510, 8192, 4), (300, 1030, 4),
     (64, 64, 4), (8, 512, 2), (100_000, 30_000, 4)],
)
def test_gram_blocks_rule(n, d, itemsize):
    b = covariance.gram_blocks(n, d, itemsize)
    n_pad, d_pad = -(-n // b.bn) * b.bn, -(-d // b.bd) * b.bd
    t = d_pad // b.bd
    assert (b.tiles, b.full_tiles) == (t * (t + 1) // 2, t * t)
    assert n_pad % b.bn == 0 and d_pad % b.bd == 0
    assert n_pad - n < b.bn and d_pad - d < b.bd
    assert b.vmem_bytes == covariance.gram_vmem_bytes(b.bn, b.bd, itemsize)
    assert b.vmem_bytes <= covariance.VMEM_BUDGET


def test_gram_blocks_paper_pca():
    """At paper_pca's shard the kernel launches 36 of the 64 tiles."""
    b = covariance.gram_blocks(65536, 8192, 4)
    assert (b.bn, b.bd, b.tiles, b.full_tiles) == (1024, 1024, 36, 64)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(min_value=8, max_value=200),
    d=st.integers(min_value=8, max_value=160),
    seed=st.integers(min_value=0, max_value=1000),
)
def test_gram_property(n, d, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    got = covariance.gram(x, bn=64, bd=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.gram(x)), atol=1e-3 * d
    )


def _dot_precisions(jaxpr, inside=None, found=None):
    """(pallas kernel name or None, precision) of every dot_general."""
    found = [] if found is None else found
    for e in jaxpr.eqns:
        where = inside
        if e.primitive.name == "pallas_call":
            where = str(e.params["name"])
        if e.primitive.name == "dot_general":
            found.append((where, e.params["precision"]))
        for p in e.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dot_precisions(sub, where, found)
    return found


def test_gram_precision_on_pipeline_path():
    """The local PCA traces the Gram kernel's product at the default
    precision (one bf16 pass, f32 sums) and the solve's at HIGHEST."""
    from repro.core import distributed

    jx = jax.make_jaxpr(
        lambda x: distributed._local_pca_basis(
            x, 4, solver="subspace", iters=2, backend="pallas")
    )(jax.ShapeDtypeStruct((256, 512), jnp.float32))
    dots = _dot_precisions(jx.jaxpr)
    gram = [p for w, p in dots if w == "gram"]
    assert gram and all(p is None for p in gram)
    highest = (jax.lax.Precision.HIGHEST,) * 2
    solve = [p for w, p in dots if w != "gram"]
    assert solve and all(p == highest for p in solve)


# --------------------------------------------------- procrustes stages ----
@pytest.mark.parametrize("m,d,r", [(2, 64, 4), (6, 500, 16), (3, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_gram(m, d, r, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    vs = jax.random.normal(k1, (m, d, r), dtype=dtype)
    rf = jax.random.normal(k2, (d, r), dtype=dtype)
    got = procrustes_align.batched_gram(vs, rf, bk=128, interpret=True)
    want = ref.batched_gram(vs, rf)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=TOL[dtype] * d, rtol=1e-2
    )


@pytest.mark.parametrize("m,d,r", [(2, 64, 4), (6, 500, 16), (8, 1000, 32)])
def test_align_average(m, d, r):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    vs = jax.random.normal(k1, (m, d, r))
    zs = jax.random.normal(k2, (m, r, r))
    got = procrustes_align.align_average(vs, zs, bd=128, interpret=True)
    want = ref.align_average(vs, zs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_kernelized_algorithm1_end_to_end():
    """Algorithm 1 with every stage routed through the kernels must equal
    the pure-jnp Algorithm 1."""
    from repro.core import procrustes_fix_average, qr_orthonormalize

    key = jax.random.PRNGKey(3)
    m, d, r = 5, 160, 8
    vs = jnp.stack(
        [
            jnp.linalg.qr(jax.random.normal(k, (d, r)))[0]
            for k in jax.random.split(key, m)
        ]
    )
    refsol = vs[0]
    g = procrustes_align.batched_gram(vs, refsol, bk=64, interpret=True)
    u, _, wt = jnp.linalg.svd(g)
    zs = u @ wt
    vbar = procrustes_align.align_average(vs, zs, bd=64, interpret=True)
    got = qr_orthonormalize(vbar)
    want = procrustes_fix_average(vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


# ------------------------------------------------------ flash attention ----
@pytest.mark.parametrize(
    "b,hq,hkv,s,t,d",
    [
        (1, 2, 2, 128, 128, 64),   # MHA
        (2, 4, 2, 256, 256, 64),   # GQA 2:1
        (1, 8, 1, 128, 128, 32),   # MQA
        (1, 2, 1, 96, 160, 64),    # uneven s/t, padding path
        (1, 2, 2, 32, 256, 64),    # suffix queries (chunked prefill)
    ],
)
def test_flash_attention_shapes(b, hq, hkv, s, t, d):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, t, d))
    v = jax.random.normal(ks[2], (b, hkv, t, d))
    got = flash_attention.flash_attention(q, k, v, bq=64, bk=64, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("window", [16, 64, 1024])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    got = flash_attention.flash_attention(
        q, k, v, window=window, bq=64, bk=64, interpret=True
    )
    want = ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64), dtype=jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), dtype=jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), dtype=jnp.bfloat16)
    got = flash_attention.flash_attention(q, k, v, bq=64, bk=64, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
    )


@settings(max_examples=10, deadline=None)
@given(
    s=st.sampled_from([64, 128, 192]),
    bq=st.sampled_from([32, 64]),
    bk=st.sampled_from([32, 64]),
    seed=st.integers(min_value=0, max_value=100),
)
def test_flash_block_size_invariance(s, bq, bk, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, 2, s, 32))
    k = jax.random.normal(ks[1], (1, 2, s, 32))
    v = jax.random.normal(ks[2], (1, 2, s, 32))
    got = flash_attention.flash_attention(q, k, v, bq=bq, bk=bk, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ----------------------------------------------------------------- ops ----
def test_ops_dispatch_cpu():
    """On CPU the default path must be the oracle (no interpret overhead)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    np.testing.assert_allclose(
        np.asarray(ops.gram(x)), np.asarray(ref.gram(x)), atol=1e-5
    )
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 16, 8))
    np.testing.assert_allclose(
        np.asarray(ops.attention(q, q, q)),
        np.asarray(ref.attention(q, q, q)),
        atol=1e-5,
    )


def test_empirical_covariance_backend_switch():
    from repro.core import empirical_covariance

    x = jax.random.normal(jax.random.PRNGKey(4), (100, 60))
    a = empirical_covariance(x)
    b = empirical_covariance(x, backend="pallas")  # interpret mode on CPU
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
