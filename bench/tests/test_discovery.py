"""The harness finds every cell's pieces by name, from BENCHMARK.json and
the files beside it, so that a new cell, traffic mix or metric is added
without editing a file that is already there."""

import json
import os
import re

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_a_new_traffic_mix_and_metric_are_found_by_name(tmp_path):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "traffic" / "burst_queries.json").write_text(
        json.dumps({"driver": "open_loop_service", "query_rate_per_s": 40}))
    (tmp_path / "metrics" / "queue_depth.serve.py").write_text(
        "def read(ctx):\n    return ctx.counters.get('depth')\n")
    mix = harness.traffic("burst_queries", bench_dir=str(tmp_path))
    assert mix["query_rate_per_s"] == 40
    reader = harness.metric_reader("queue_depth.serve", bench_dir=str(tmp_path))
    ctx = harness.Context(workload={"name": "x", "chips": 1}, cfg={},
                          traffic=mix, seed=0, devices=[],
                          counters={"depth": 3})
    assert reader.read(ctx) == 3


def test_metrics_are_reported_where_they_say():
    entries = [
        {"name": "a", "moves": "lat"},
        {"name": "b", "moves": "rate"},
        {"name": "c", "moves": "lat", "workloads": ["other"]},
        {"name": "d", "moves": "rate", "workloads": ["cell"]},
    ]
    cell = {"name": "cell"}
    got = [m["name"] for m in harness.metrics_for(entries, cell, ["lat"])]
    assert got == ["a", "d"]
    e2e = [{"name": "setup_s"}, {"name": "lat", "workloads": ["cell"]},
           {"name": "rate", "workloads": ["other"]}]
    assert [m["name"] for m in harness.metrics_for(e2e, cell)] == [
        "setup_s", "lat"]


def test_every_cell_of_the_benchmark_resolves():
    bench = harness.benchmark()
    assert {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"} <= set(bench)
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(harness.ROOT, c["file"]))
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e_names
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs), "a config and traffic pair repeats"
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        cfg = harness.config(bench, w["config"])
        mix = harness.traffic(w["traffic"])
        drv = harness.driver(mix["driver"])
        for fn in ("setup", "window", "check"):
            assert callable(getattr(drv, fn))
        assert cfg["limits"]
        assert mix.get("shards", w["chips"]) == w["chips"], w["name"]
        reported = harness.metrics_for(bench["end_to_end"], w)
        names = [m["name"] for m in reported]
        assert "setup_s" in names and len(names) >= 2
        layer = harness.metrics_for(bench["per_layer"], w, names)
        assert layer, w["name"]
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e_names
        assert callable(harness.metric_reader(m["name"]).read)
