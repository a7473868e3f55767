"""The program's profiler spans and the service's counters.

``distributed_pca`` and ``SubspaceService`` mark each host step with a
``jax.profiler.TraceAnnotation`` named ``repro.<layer>[.<step>]`` whose
ids (``job``, ``step``, ``refresh``, ``query``) ride along as keyword
arguments; the benchmark's per-layer readers find them in the run's
``.xplane.pb``.  These tests trace small calls with the CPU profiler and
read the spans back the way those readers do: host plane, one line per
thread, ids as the events' stats.  They also pin the ``stats`` counters
(``queries``, ``syncs``, ``sync_wait_s``) and that tracing changes no
result.
"""

from __future__ import annotations

import glob
import os
import sys
import threading
import types

import jax
import numpy as np
import pytest

from repro.core.distributed import _mesh_program, distributed_pca
from repro.data import synthetic as syn
from repro.launch.mesh import make_aggregation_mesh
from repro.stream.service import SubspaceService

D, R, CHUNK, CADENCE, STEPS = 32, 4, 64, 2, 5
QUERIES = 3


def _rows(n, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    _, _, factor = syn.covariance_from_spectrum(k1, syn.spectrum_m1(D, R))
    return syn.sample_gaussian(k2, factor, n)


def _service(mesh):
    return SubspaceService(mesh, D, R, cadence=CADENCE, solver="subspace",
                           iters=10)


def _workload(mesh, rows, chunks, queries):
    """Two PCA calls, then a service fed ``STEPS`` chunks, queried and
    asked for its drift; returns every result as a host array."""
    out = {"pca": [np.asarray(distributed_pca(rows, mesh, R)) for _ in
                   range(2)]}
    svc = _service(mesh)
    bases = []
    for c in chunks:
        svc.observe(c[None])
        bases.append(np.asarray(svc.basis))
    out["service_basis"] = bases
    out["projection"] = [np.asarray(svc.project(q)) for q in queries]
    out["drift"] = [svc.drift()]
    return out


def _spans(log_dir):
    """Every ``repro.*`` host span: name, start, end, ids, thread line."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append(types.SimpleNamespace(
                        name=e.name, start=e.start_ns,
                        end=e.start_ns + e.duration_ns,
                        ids={k: v for k, v in e.stats}, thread=i))
    return out


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """The workload untraced, then again under the profiler."""
    mesh = make_aggregation_mesh()
    rows = _rows(256)
    chunks = _rows(STEPS * CHUNK, seed=1).reshape(STEPS, CHUNK, D)
    queries = _rows(QUERIES * 8, seed=2).reshape(QUERIES, 8, D)
    plain = _workload(mesh, rows, chunks, queries)
    # The traced run's first PCA call then builds its program again.
    _mesh_program.cache_clear()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        traced = _workload(mesh, rows, chunks, queries)
    finally:
        jax.profiler.stop_trace()
    return types.SimpleNamespace(plain=plain, traced=traced,
                                 spans=_spans(log_dir))


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _parent(spans, child, parent_name):
    """The ``parent_name`` span on the child's thread that holds it."""
    held = [s for s in _named(spans, parent_name)
            if s.thread == child.thread and s.start <= child.start
            and child.end <= s.end]
    assert len(held) == 1, (child, held)
    return held[0]


@pytest.mark.parametrize("child", ["repro.pca.plan", "repro.pca.dispatch"])
def test_pca_steps_nest_in_their_call_under_one_job_id(run, child):
    calls = _named(run.spans, "repro.pca")
    kids = _named(run.spans, child)
    assert len(calls) == len(kids) == 2
    for k in kids:
        assert k.ids["job"] == _parent(run.spans, k, "repro.pca").ids["job"]


def test_each_pca_call_gets_its_own_job_id(run):
    jobs = [s.ids["job"] for s in _named(run.spans, "repro.pca")]
    assert jobs[1] == jobs[0] + 1


def test_pca_dispatch_says_whether_it_built_the_program(run):
    # The first call builds the program, the second reuses it.
    built = [s.ids["built"] for s in _named(run.spans, "repro.pca.dispatch")]
    assert built == [1, 0]


def test_pca_dispatch_names_the_local_form(run):
    # 256 rows of width 32 on one device: the covariance form.
    forms = [s.ids["form"] for s in _named(run.spans, "repro.pca.dispatch")]
    assert forms == ["cov", "cov"]


# (span, the span that holds it, the id they share)
SERVICE_NESTING = [
    ("repro.service.update", "repro.service.observe", "step"),
    ("repro.service.refresh", "repro.service.observe", "step"),
    ("repro.service.refresh.wait_count", "repro.service.refresh", "refresh"),
    ("repro.service.refresh.dispatch", "repro.service.refresh", "refresh"),
    ("repro.service.refresh.wait_jump", "repro.service.refresh", "refresh"),
]


@pytest.mark.parametrize("child,parent,key", SERVICE_NESTING,
                         ids=[c for c, _, _ in SERVICE_NESTING])
def test_service_steps_nest_and_share_their_id(run, child, parent, key):
    kids = _named(run.spans, child)
    assert kids
    for k in kids:
        assert k.ids[key] == _parent(run.spans, k, parent).ids[key]


def test_service_span_ids_count_steps_and_refreshes(run):
    steps = [s.ids["step"] for s in _named(run.spans, "repro.service.observe")]
    assert steps == list(range(1, STEPS + 1))
    refreshes = _named(run.spans, "repro.service.refresh")
    assert [s.ids["refresh"] for s in refreshes] == list(
        range(len(refreshes)))
    # Every refresh after the first reads the basis jump; the first has no
    # basis to compare with.
    jumps = _named(run.spans, "repro.service.refresh.wait_jump")
    assert [s.ids["refresh"] for s in jumps] == list(
        range(1, len(refreshes)))


def test_queries_and_drift_carry_their_ids(run):
    queries = _named(run.spans, "repro.service.project")
    assert [s.ids["query"] for s in queries] == list(range(QUERIES))
    (drift,) = _named(run.spans, "repro.service.drift")
    assert drift.ids["step"] == STEPS


@pytest.mark.parametrize("what", ["pca", "service_basis", "projection",
                                  "drift"])
def test_results_are_bit_identical_under_the_profiler(run, what):
    for a, b in zip(run.plain[what], run.traced[what], strict=True):
        np.testing.assert_array_equal(a, b)


@pytest.fixture(scope="module")
def mesh():
    return make_aggregation_mesh()


@pytest.mark.parametrize("n", [0, 1, 5])
def test_queries_counts_project_calls(mesh, n):
    svc = _service(mesh)
    svc.observe(_rows(CHUNK)[None])
    q = _rows(8, seed=3)
    for _ in range(n):
        svc.project(q)
    assert svc.stats["queries"] == n


def test_queries_count_is_exact_across_threads(mesh):
    svc = _service(mesh)
    svc.observe(_rows(CHUNK)[None])
    q = _rows(8, seed=3)
    svc.project(q).block_until_ready()
    threads_n, calls = 2 * (os.cpu_count() or 1) + 2, 40

    def ask():
        for _ in range(calls):
            svc.project(q)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=ask) for _ in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert svc.stats["queries"] == 1 + threads_n * calls


@pytest.mark.parametrize("cadence", [1, 3])
def test_syncs_count_each_refreshs_blocking_reads(mesh, cadence):
    svc = SubspaceService(mesh, D, R, cadence=cadence, solver="subspace",
                          iters=10)
    chunks = _rows(7 * CHUNK, seed=4).reshape(7, 1, CHUNK, D)
    syncs, waited = [0], [0.0]
    for c in chunks:
        before = svc.stats["refreshes"]
        svc.observe(c)
        st = svc.stats
        if st["refreshes"] == before:
            assert st["syncs"] == syncs[-1]
        else:
            # The first refresh reads the row count; each later one also
            # reads the basis jump.
            assert st["syncs"] == syncs[-1] + (1 if before == 0 else 2)
        assert st["sync_wait_s"] >= waited[-1]
        syncs.append(st["syncs"])
        waited.append(st["sync_wait_s"])
    assert svc.stats["refreshes"] == 1 + 6 // cadence
    assert waited[-1] > 0


def test_drift_counts_one_blocking_read(mesh):
    svc = _service(mesh)
    svc.observe(_rows(CHUNK)[None])
    before = svc.stats
    svc.drift()
    after = svc.stats
    assert after["syncs"] == before["syncs"] + 1
    assert after["sync_wait_s"] >= before["sync_wait_s"]
