"""The two cells added with genotype PCA, whole runs at a CPU size.

``gpca_ukb_site``: the program reads correct; the control (the reference
with the products at ``default``, the configuration's
``control_precision``) and two planted faults read not correct: rows
standardized by the mean and frequencies of all resident sites pooled
instead of the site's own, and one iteration fewer.  Thirty iterations
leave the span short of convergence (λ₆/λ₅ ≈ 0.94 here; λ₄₁/λ₄₀ ≈ 0.65
at UK Biobank's widths), so the 29th iterate is another span.

``serve_query_only``: a window with no chunk runs, and its check passes.
"""

import functools
import inspect

import jax.numpy as jnp
import pytest

import repro.core.distributed as distributed
from bench import data, genotype_data, harness

GPCA = {"config": {"d": 2048, "r": 5, "rows_per_shard": 512,
                   "genotypes": {"model": "admixture", "populations": 6,
                                 "fst": 0.01, "p_lo": 0.05, "p_hi": 0.95,
                                 "alpha": 0.1}}}
QUERY_ONLY = {"config": {"d": 256, "r": 8, "rows_per_chunk": 512,
                         "query_batch": 32},
              "traffic": {"query_rate_per_s": 50}}
SEED = 2**31 + 29


@pytest.fixture
def row_form(monkeypatch):
    """A device too small for the (d, d) float32 covariance at the CPU
    size, so the program takes the row form, as on the chip at d=147,604."""
    d = GPCA["config"]["d"]
    monkeypatch.setattr(distributed, "_device_bytes", lambda dev: 4 * d * d - 1)


def _run(cell, overrides, system="program", seed=SEED):
    return harness.run_cell(cell, seed, 1.5, False, require_tpu=False,
                            system=system, overrides=overrides)


@pytest.mark.parametrize("system", ["program", "control"])
def test_gpca_program_is_correct_and_control_is_not(row_form, system):
    out = _run("gpca_ukb_site", GPCA, system)
    assert out["correct"] == (system == "program"), out["checks"]


def _pooled_standardization(real):
    """The fault: every job's rows standardized with the mean and allele
    frequencies of all resident sites pooled (a global standardization),
    not its own site's; then solved as plain float rows."""
    bench = harness.benchmark()
    cfg = {**harness.config(bench, "ukb_genotype_pca"), **GPCA["config"]}
    mix = harness.traffic("genotype_jobs_back_to_back")
    sites = genotype_data.sites(data.seed_key(SEED), cfg,
                                int(mix["datasets"]), int(mix["shards"]))
    pooled = jnp.concatenate(sites)
    n = pooled.shape[0]
    s = jnp.sum(pooled, axis=0, dtype=jnp.int32).astype(jnp.float32)
    mean = s / n
    p = (1.0 + s) / (2.0 + 2.0 * n)
    scale = jnp.sqrt(p * (1.0 - p))

    @functools.wraps(real)
    def job(counts, mesh, r, *, standardize, **kw):
        x = (counts.astype(jnp.float32) - mean) / scale
        return real(x, mesh, r, standardize=None, **kw)
    job.__signature__ = inspect.signature(real)
    return job


def _one_iteration_fewer(real):
    @functools.wraps(real)
    def job(counts, mesh, r, *, iters, **kw):
        return real(counts, mesh, r, iters=iters - 1, **kw)
    return job


@pytest.mark.parametrize("fault", [_pooled_standardization,
                                   _one_iteration_fewer])
def test_gpca_faults(row_form, monkeypatch, fault):
    monkeypatch.setattr(distributed, "distributed_pca",
                        fault(distributed.distributed_pca))
    out = _run("gpca_ukb_site", GPCA)
    assert not out["correct"], out["checks"]


def test_gpca_refuses_a_program_without_standardize(monkeypatch):
    def old(samples, mesh, r, *, n_iter=1, solver="eigh", iters=30,
            backend=None):
        raise AssertionError("never called")

    monkeypatch.setattr(distributed, "distributed_pca", old)
    with pytest.raises(TypeError, match="standardize"):
        _run("gpca_ukb_site", GPCA)


def test_query_only_window_sends_no_chunk_and_is_correct(monkeypatch):
    from repro.stream import SubspaceService

    chunks = []
    real = SubspaceService.observe

    def observe(self, batches):
        chunks.append(1)
        return real(self, batches)

    monkeypatch.setattr(SubspaceService, "observe", observe)
    out = _run("serve_query_only", QUERY_ONLY)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"query_p95_ms", "setup_s"}
    assert out["failed"] == 0 and out["attempted"] > 0
    # Set-up's chunks and no other.
    assert len(chunks) == harness.traffic("queries_only")["warmup_chunks"]


def test_entry_host_ms_pca_reads_genotype_calls(monkeypatch, tmp_path):
    """A CPU trace of two genotype calls: ``entry_host_ms.pca``, which the
    genotype cell reports too, averages their ``repro.pca`` spans."""
    import types

    import jax

    from bench import program_spans as ps
    from bench import trace_reduce as tr
    from repro.data import genotypes
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    kf, kc = jax.random.split(jax.random.PRNGKey(0))
    counts = genotypes.genotype_rows(
        kc, genotypes.population_frequencies(kf, 128, 4, fst=0.05), 64,
        alpha=0.1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready([
            distributed.distributed_pca(counts, mesh, 3,
                                        standardize="patterson",
                                        solver="subspace")
            for _ in range(2)])
    finally:
        jax.profiler.stop_trace()
    spans = ps.load(tr.find_xplane(str(tmp_path)))
    calls = ps.named(spans, "repro.pca")
    assert len(calls) == 2
    monkeypatch.setattr(harness, "trace_dir", lambda name: str(tmp_path))
    ctx = types.SimpleNamespace(workload={"name": "gpca_ukb_site"},
                                trace=object(),
                                window_ns=(min(s.start_ns for s in spans) - 1,
                                           max(s.end_ns for s in spans) + 1))
    reader = harness.metric_reader("entry_host_ms.pca")
    assert reader.read(ctx) == pytest.approx(
        sum(s.dur_ns for s in calls) / 2 * 1e-6)


# The compiled loop's event name on a v5e, shortened after the carry's
# fourth element (the shard_map program's carry holds the same types).
ROWOP_WHILE = ("%while.10 = (s32[]{:T(128)}, f32[147604,40]{0,1:T(8,128)}, "
               "f32[147604]{0:T(1024)S(1)}, s8[18510,147604]{1,0:T(8,128)"
               "(4,1)}, f32[147604]{0:T(1024)}) while(%tuple.87)")
COV_WHILE = ("%while.3 = (s32[]{:T(128)}, f32[8192,128]{1,0:T(8,128)}, "
             "f32[8192,8192]{1,0:T(8,128)}) while(%tuple.5)")


def test_rowop_matcher_finds_the_row_form_loop_only():
    from bench import trace_reduce as tr

    match = harness.metric_reader("rowop_ms.gpca").matcher(18510, 147604, 40)
    assert match(tr.Event(ROWOP_WHILE, 0, 1))
    assert not match(tr.Event(COV_WHILE, 0, 1))


def test_rowop_least_time_is_bound_by_reading_the_counts():
    bench = harness.benchmark()
    cfg = harness.config(bench, "ukb_genotype_pca")
    peaks = harness.chip_peaks("TPU v5 lite")
    least, bound = harness.metric_reader("rowop_roofline.gpca").least_time_s(
        cfg["rows_per_shard"], cfg["d"], cfg["r"], cfg["solver_iters"], peaks)
    assert bound == "memory"
    assert least == pytest.approx(30 * 18510 * 147604 / 819e9)
