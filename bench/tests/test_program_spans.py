"""The readers of the program's own spans: ``bench/program_spans.py`` and
the per-layer metrics built on it."""

import os
import shutil
import types

import numpy as np
import pytest

from bench import harness
from bench import program_spans as ps
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "program_spans_tpu.xplane.pb")
TPU0 = "/device:TPU:0"


def span(name, start, end, thread=0, **ids):
    return ps.Span(name, float(start), float(end), tuple(ids.items()), thread)


def module(start, name="jit_project(77)"):
    return tr.Event(name, float(start), float(start) + 5)


def ctx_for(cell, window, modules=()):
    trace = tr.Trace({TPU0: {tr.MODULE_LINE: list(modules)}}, [])
    return types.SimpleNamespace(
        workload={"name": cell, "chips": 1}, trace=trace, window_ns=window,
        counters={"trace_devices": [TPU0]})


@pytest.fixture
def hand_spans(monkeypatch, tmp_path):
    """Serves the given spans as the run's trace file holds them."""
    def use(spans):
        monkeypatch.setattr(harness, "trace_dir", lambda name: str(tmp_path))
        (tmp_path / "run.xplane.pb").write_bytes(b"")
        monkeypatch.setattr(ps, "load", lambda path: tuple(spans))

    return use


def test_only_spans_wholly_inside_the_window_count():
    spans = [span("repro.pca", 0, 50), span("repro.pca", 40, 90),
             span("repro.pca", 95, 120)]
    assert ps.inside(spans, 30, 100) == [spans[1]]


def test_spans_group_by_their_ids():
    a, b = span("x", 0, 1, refresh=3), span("y", 1, 2, refresh=3)
    c, d = span("x", 2, 3, refresh=4), span("z", 3, 4, step=9)
    assert ps.by_id([a, b, c, d], "refresh") == {3: [a, b], 4: [c]}
    assert d.id("step") == 9 and d.id("refresh") is None


def test_entry_host_ms_is_the_mean_whole_call_in_the_window(hand_spans):
    hand_spans([span("repro.pca", 0, 3e6, job=0),        # cut by the edge
                span("repro.pca.plan", 11e6, 12e6, job=1),
                span("repro.pca", 10e6, 14e6, job=1),
                span("repro.pca", 20e6, 26e6, job=2)])
    got = harness.metric_reader("entry_host_ms.pca").read(
        ctx_for("pca_1chip", (1e6, 30e6)))
    assert got == pytest.approx(5.0)


def test_refresh_wait_ms_sums_both_waits_of_each_refresh(hand_spans):
    hand_spans([
        span("repro.service.refresh", 0, 40, refresh=1, step=4),
        span("repro.service.refresh.wait_count", 0, 10, refresh=1),
        span("repro.service.refresh.wait_jump", 30, 40, refresh=1),
        span("repro.service.refresh", 100e6, 140e6, refresh=2, step=8),
        span("repro.service.refresh.wait_count", 100e6, 102e6, refresh=2),
        span("repro.service.refresh.dispatch", 102e6, 103e6, refresh=2),
        span("repro.service.refresh.wait_jump", 110e6, 140e6, refresh=2),
        span("repro.service.refresh", 200e6, 240e6, refresh=3, step=12),
        span("repro.service.refresh.wait_count", 200e6, 204e6, refresh=3),
        span("repro.service.refresh.wait_jump", 220e6, 240e6, refresh=3),
    ])
    # Refresh 1 starts before the window and is left out.
    got = harness.metric_reader("refresh_wait_ms.serve").read(
        ctx_for("serve_refresh", (50, 300e6)))
    assert got == pytest.approx((32 + 24) / 2)


def test_queries_take_the_programs_in_order(hand_spans):
    reader = harness.metric_reader("query_wait_ms.serve")
    ms = 1e6
    queries = [span("repro.service.project", t * ms, t * ms + 1, thread=2,
                    query=i) for i, t in enumerate((100, 200, 210, 400))]
    # A program long before any query, the first query's drawn 1 ms before
    # its span (the device's clock runs early in the trace), the next two
    # queued behind other work, and the last one's after the window.
    mods = [module(t * ms) for t in (50, 99, 330, 340, 520)]
    assert reader.waits_ns(queries, [m.start_ns for m in mods], 500 * ms) \
        == [-1 * ms, 130 * ms, 130 * ms]
    # Another program on the module line is not a query's.
    mods.insert(2, module(201 * ms, "jit_shard_fn(5)"))
    hand_spans(queries)
    got = reader.read(ctx_for("serve_refresh", (0, 500 * ms), mods))
    assert got == pytest.approx(np.percentile([-1, 130, 130], 95))


@pytest.mark.parametrize("metric,cell", [
    ("entry_host_ms.pca", "pca_1chip"),
    ("refresh_wait_ms.serve", "serve_refresh"),
    ("query_wait_ms.serve", "serve_refresh"),
])
def test_no_program_span_reads_as_nothing(hand_spans, metric, cell):
    """A program without spans, as the parent of this change or a control,
    leaves every reader silent: no number, never 0."""
    hand_spans([])
    reader = harness.metric_reader(metric)
    assert reader.read(ctx_for(cell, (0, 1e9), [module(10)])) is None
    hand_spans([span("repro.service.observe", 0, 10, step=1)])
    assert reader.read(ctx_for(cell, (0, 1e9), [module(10)])) is None


def test_a_trace_without_program_spans_reads_as_nothing(monkeypatch, tmp_path):
    shutil.copy(os.path.join(DATA, "small_tpu_trace.xplane.pb"), tmp_path)
    monkeypatch.setattr(harness, "trace_dir", lambda name: str(tmp_path))
    trace = tr.load(tr.find_xplane(str(tmp_path)))
    ctx = types.SimpleNamespace(workload={"name": "pca_1chip", "chips": 1},
                                trace=trace, window_ns=trace.window(),
                                counters={"trace_devices": [TPU0]})
    assert ps.window_spans(ctx) is None
    assert harness.metric_reader("entry_host_ms.pca").read(ctx) is None


@pytest.fixture
def recorded(monkeypatch, tmp_path):
    """The recorded chip trace as a run's trace directory holds it."""
    shutil.copy(RECORDED, tmp_path)
    monkeypatch.setattr(harness, "trace_dir", lambda name: str(tmp_path))
    trace = tr.load(RECORDED)
    return types.SimpleNamespace(
        workload={"name": "serve_refresh", "chips": 1}, trace=trace,
        window_ns=trace.window(),
        counters={"trace_devices": trace.device_names()[:1]})


def test_the_recorded_trace_holds_every_program_span(recorded):
    spans = ps.window_spans(recorded)
    names = {s.name for s in spans}
    assert names >= {"repro.pca", "repro.pca.plan", "repro.pca.dispatch",
                     "repro.service.observe", "repro.service.update",
                     "repro.service.refresh",
                     "repro.service.refresh.wait_count",
                     "repro.service.refresh.dispatch",
                     "repro.service.refresh.wait_jump",
                     "repro.service.project"}
    # Queries come from a thread of their own.
    threads = {s.name: s.thread for s in spans}
    assert threads["repro.service.project"] != threads["repro.service.observe"]
    assert os.path.getsize(RECORDED) <= 200_000


@pytest.mark.parametrize("metric", ["entry_host_ms.pca",
                                    "refresh_wait_ms.serve",
                                    "query_wait_ms.serve"])
def test_the_recorded_trace_reduces_to_finite_values(recorded, metric):
    got = harness.metric_reader(metric).read(recorded)
    assert got is not None and np.isfinite(got) and abs(got) < 10_000
