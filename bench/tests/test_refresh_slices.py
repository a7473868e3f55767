"""The reader of ``refresh_slices.serve``: slices per refresh, from the
service's ``repro.service.refresh.slice`` spans."""

import types

import pytest

from bench import harness
from bench import program_spans as ps

MS = 1e6


def span(name, start, end, **ids):
    return ps.Span(name, float(start), float(end), tuple(ids.items()), 0)


def refresh(n, start, slices):
    """A refresh span and its slice spans, 1 ms each."""
    out = [span("repro.service.refresh", start, start + 40 * MS, refresh=n,
                step=4 * n)]
    out += [span("repro.service.refresh.slice", start + (j + 1) * MS,
                 start + (j + 2) * MS, refresh=n, slice=j)
            for j in range(slices)]
    return out


@pytest.fixture
def read(monkeypatch, tmp_path):
    """Reads the metric from the given spans, as the run's trace file
    would hold them, over the window (0, 1 s)."""
    def run(spans):
        monkeypatch.setattr(harness, "trace_dir", lambda name: str(tmp_path))
        (tmp_path / "run.xplane.pb").write_bytes(b"")
        monkeypatch.setattr(ps, "load", lambda path: tuple(spans))
        ctx = types.SimpleNamespace(
            workload={"name": "serve_refresh", "chips": 1}, trace=object(),
            window_ns=(0.0, 1000 * MS), counters={})
        return harness.metric_reader("refresh_slices.serve").read(ctx)

    return run


def test_slices_per_refresh_in_the_window(read):
    # The first refresh starts before the window, the last ends after it:
    # only the two whole refreshes count.
    spans = (refresh(0, -10 * MS, 3) + refresh(1, 100 * MS, 8)
             + refresh(2, 300 * MS, 8) + refresh(3, 980 * MS, 8))
    assert read(spans) == pytest.approx(8.0)


def test_a_program_without_slice_spans_reads_as_nothing(read):
    assert read([]) is None
    assert read([span("repro.service.refresh", 10 * MS, 50 * MS, refresh=0,
                      step=4)]) is None


def test_the_metric_is_declared_for_the_refresh_cell():
    bench = harness.benchmark()
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "refresh_slices.serve"]
    assert entry["workloads"] == ["serve_refresh"]
    assert entry["moves"] == "query_p95_ms"
