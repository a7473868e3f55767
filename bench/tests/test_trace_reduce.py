"""The reduction from a profiler trace to the benchmark's device numbers."""

import os
import types

import pytest

from bench import harness
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, start, end):
    return tr.Event(name, float(start), float(end))


def test_busy_is_the_union_of_overlapping_ops():
    ops = [ev("a", 0, 10), ev("b", 5, 15), ev("c", 12, 14), ev("d", 20, 30)]
    assert tr.busy_ns(ops, 0, 40) == 25
    assert tr.busy_ns(ops, 8, 22) == 9  # clipped to the window
    assert tr.idle_gaps(ops, 0, 40) == [(15, 20), (30, 40)]


def test_idle_gaps_are_labelled_by_the_host_span_open_in_them():
    ops = [ev("op", 0, 10), ev("op", 40, 50), ev("op", 52, 60)]
    host = [ev("bench.window", 0, 60), ev("bench.job", 5, 45),
            ev("lower_sharding_computation", 12, 38), ev("tiny", 20, 21)]
    trace = tr.Trace({"/device:TPU:0": {tr.OP_LINE: ops}}, host)
    gaps = tr.labelled_gaps(trace, "/device:TPU:0", 0, 60)
    # The 30 ns gap: three spans cover half of it or more; the shortest of
    # those is the most specific.  The 2 ns gap has only the window.
    assert gaps[0] == ("lower_sharding_computation", pytest.approx(30e-9))
    assert gaps[1] == ("bench.window", pytest.approx(2e-9))
    assert tr.label((100, 110), host) == "(no host span)"


def test_label_falls_back_to_the_largest_cover():
    host = [ev("a", 0, 3), ev("b", 6, 10)]
    assert tr.label((0, 10), host) == "b"


def test_loops_are_not_counted_twice_in_the_top_ops():
    loop = ev("%while.3 = (s32[], f32[8,8]) while(...)", 0, 100)
    body = [ev("%fusion.1 = f32[8,8] fusion(...)", 10, 40),
            ev("%fusion.1 = f32[8,8] fusion(...)", 50, 90)]
    other = ev("%gram.1 = f32[8,8] custom-call(...)", 100, 120)
    mods = [ev("jit_shard_fn(123)", 0, 130)]
    trace = tr.Trace({"/device:TPU:0": {tr.OP_LINE: [loop, *body, other],
                                        tr.MODULE_LINE: mods}}, [])
    assert tr.leaves([loop, *body, other]) == [*body, other]
    top = tr.top_ops(trace, 0, 200, ["/device:TPU:0"])
    assert top == [("jit_shard_fn/fusion.1", pytest.approx(70e-9)),
                   ("jit_shard_fn/gram.1", pytest.approx(20e-9))]


def test_exposed_collective_time_leaves_out_overlapped_compute():
    coll = tr.Matcher(r"^all-gather", fields=("short",))
    ops = [ev("%all-gather.1 = f32[4,8] all-gather(...)", 0, 10),
           ev("%fusion.2 = f32[8] fusion(...)", 5, 8),
           ev("%all-gather.2 = f32[4,8] all-gather(...)", 20, 22)]
    assert tr.exposed_ns(ops, coll, 0, 100) == 7 + 2


def _metric(name):
    return harness.metric_reader(name)


def test_layer_matchers_find_their_events():
    gram = _metric("cov_roofline.pca").MATCH
    assert gram(ev("%gram.1 = f32[8192,8192]{1,0} custom-call(f32[65536,8192]"
                   " %x_shard.1)", 0, 1))
    assert not gram(ev("%batched_gram.3 = f32[1,128,128] custom-call()", 0, 1))
    solve = _metric("solve_ms.pca").matcher(8192, 128)
    assert solve(ev("%while.96 = (s32[]{:T(128)}, f32[8192,128]{0,1:T(8,128)"
                    "S(1)}, f32[8192,8192]{1,0:T(8,128)}, s32[]{:T(128)}) "
                    "while((s32[]{:T(128)}) %tuple.160), condition=%c", 0, 1))
    assert not solve(ev("%while.97 = (s32[]{:T(128)}, f32[1,128,128]{2,1,0}, "
                        "f32[2,3]{1,0}) while(%t), condition=%c", 0, 1))
    refresh = _metric("refresh_ms.serve").MODULE
    assert refresh(ev("jit_shard_fn(1060097041181296536)", 0, 1))
    assert not refresh(ev("jit__masked_update(4767878487994078608)", 0, 1))
    coll = _metric("exposed_collective_ms.pca").COLLECTIVE
    assert coll(ev("%all-gather-start.2 = (f32[8192,128]) all-gather-start()",
                   0, 1))
    assert not coll(ev("%fusion.4 = f32[8] fusion(%all-gather.1)", 0, 1))


def test_roofline_share_refuses_a_share_over_105_percent():
    assert tr.least_time_s(197e12, 1.0, 197e12, 819e9) == (1.0, "compute")
    assert tr.least_time_s(1.0, 819e9, 197e12, 819e9) == (1.0, "memory")
    assert tr.roofline_share(1.0, 2.0) == 0.5
    assert tr.roofline_share(1.05, 1.0) == pytest.approx(1.05)
    with pytest.raises(ValueError, match="exceeds"):
        tr.roofline_share(1.06, 1.0)
    with pytest.raises(ValueError):
        tr.roofline_share(1.0, 0.0)


def test_cov_roofline_counts_the_layers_need_per_job_and_chip():
    reader = _metric("cov_roofline.pca")
    n, d = 65536, 8192
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = reader.least_time_s(n, d, peaks)
    assert bound == "compute"
    assert least == pytest.approx(n * d * (d + 1) / 197e12)
    # Two jobs on one chip, 118.7 ms of Gram kernel each.
    ops = [ev("%gram.1 = f32[8192,8192] custom-call()", 0, 118.7e6),
           ev("%gram.1 = f32[8192,8192] custom-call()", 200e6, 318.7e6)]
    trace = tr.Trace({"/device:TPU:0": {tr.OP_LINE: ops}}, [])
    ctx = types.SimpleNamespace(
        trace=trace, window_ns=(0, 400e6), chips=1, peaks=peaks,
        cfg={"rows_per_shard": n, "d": d},
        counters={"jobs": 2, "trace_devices": ["/device:TPU:0"]})
    assert reader.read(ctx) == pytest.approx(100 * least / 0.1187)
    ops[1] = ev("%gram.1 = f32[8192,8192] custom-call()", 200e6, 201e6)
    with pytest.raises(ValueError, match="exceeds"):
        ops[0] = ev("%gram.1 = f32[8192,8192] custom-call()", 0, 1e6)
        reader.read(ctx)
    ctx.trace = tr.Trace({"/device:TPU:0": {tr.OP_LINE: []}}, [])
    assert reader.read(ctx) is None  # nothing to read: no number, never 0


def test_a_recorded_tpu_trace_reduces():
    trace = tr.load(os.path.join(DATA, "small_tpu_trace.xplane.pb"))
    assert trace.device_names() == ["/device:TPU:0"]
    lo, hi = trace.window()
    busy = tr.busy_ns(trace.ops("/device:TPU:0"), lo, hi)
    assert 0 < busy < hi - lo
    assert {e.name for e in trace.host} >= {"bench.window", "bench.job"}
    top = tr.top_ops(trace, lo, hi, ["/device:TPU:0"])
    assert top and all(name.startswith("jit__lambda/") for name, _ in top)
    assert sum(s for _, s in top) == pytest.approx(busy * 1e-9, rel=0.05)
    gaps = tr.labelled_gaps(trace, "/device:TPU:0", lo, hi, n=3)
    assert len(gaps) == 3 and all(s > 0 for _, s in gaps)
