"""Streaming-service benchmark: refresh cost vs query throughput.

Times the two steady-state programs of ``repro.stream.SubspaceService``
per (d, r) x comm x bits cell and records them into the v8
``bench_aggregate`` schema (``workload`` axis):

  * ``stream-refresh`` — the cached mesh program one refresh runs: local
    top-r eigenbasis from the accumulated per-shard covariances, then one
    Procrustes round with the previously served basis as reference (no
    broadcast).  ``comm`` / ``bits`` mean what they mean on the one-shot
    collective cells.
  * ``stream-query`` — the batched projection onto the served basis
    (``comm="-"``: the hot path carries zero collective bytes, which
    ``tests/test_stream.py`` pins on the jaxpr).  The record's ``batch``
    field carries the query rows per call.

``--check`` is the serving-economics gate wired into CI bench-smoke:
with refreshes every ``--cadence`` observe steps, the *amortized* refresh
cost per step must not dominate a step's worth of query work —

    refresh_us_min / cadence  <=  max_overhead x query_us_min

per (d, r, comm, bits) cell, min-of-reps on both sides (scheduler noise
only ever inflates a wall time, same rationale as
``bench_aggregate.check``).  A violation means the service spends more
of its life re-aggregating than serving at the recorded batch size —
either the cadence is too aggressive for the topology/precision or a
refresh-path regression landed.

Run:  PYTHONPATH=src python -m benchmarks.bench_stream \
          [--tiny] [--out BENCH_stream.json] [--reps 5] [--cadence 8]
          [--comms psum,ring,hier] [--bits 32,8] [--batch 1024]
      PYTHONPATH=src python -m benchmarks.bench_stream --check BENCH.json \
          [--max-overhead 4.0]
"""

from __future__ import annotations

import argparse
import json
import statistics
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from benchmarks.bench_aggregate import SCHEMA, load

DEFAULT_SHAPES = ((1024, 16), (2048, 32))  # (d, r); m := device count
TINY_SHAPES = ((128, 4), (96, 8))
DEFAULT_COMMS = ("psum", "ring", "hier")
DEFAULT_BITS = (32, 8)


def _time_calls(fn, args, reps: int) -> Dict[str, float]:
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append((time.perf_counter() - t0) * 1e6)
    return {
        "compile_s": compile_s,
        "wall_us": statistics.median(walls),
        "wall_us_min": min(walls),
        "wall_us_max": max(walls),
        "reps": reps,
    }


def run_sweep(
    *, shapes=DEFAULT_SHAPES, comms=DEFAULT_COMMS, bits=DEFAULT_BITS,
    cadence: int = 8, batch: int = 1024, reps: int = 5, n_iter: int = 1,
) -> dict:
    from repro.launch.mesh import make_aggregation_mesh
    from repro.stream.service import SubspaceService, _safe_covs

    n_dev = len(jax.devices())
    if n_dev < 2:
        print("# stream cells skipped: single-device host "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return {"schema": SCHEMA, "meta": _meta(cadence, batch),
                "records": []}
    hier_pods = n_dev // 2 if n_dev % 2 == 0 and n_dev >= 4 else 0
    records: List[dict] = []
    for d, r in shapes:
        key = jax.random.PRNGKey(d * 1_003 + r)
        rows = jax.random.normal(key, (n_dev, 256, d), jnp.float32)
        queries = jax.random.normal(
            jax.random.fold_in(key, 1), (batch, d), jnp.float32
        )
        for comm in comms:
            hier = comm == "hier"
            if hier and not hier_pods:
                print(f"# stream/hier cells skipped: {n_dev} devices do "
                      "not tile into pods")
                continue
            mesh = make_aggregation_mesh(
                n_dev, pods=hier_pods if hier else None
            )
            for cb in bits:
                svc = SubspaceService(
                    mesh, d, r, n_iter=n_iter, cadence=cadence,
                    topology=comm, comm_bits=cb,
                )
                svc.observe(rows)  # one chunk per shard seeds the state
                covs = _safe_covs(svc._state)
                ref = svc.basis  # the observe() bootstrapped a basis
                # The default solver (eigh) refreshes in one program.
                (fn,) = svc.refresh_programs(with_ref=True)
                rec = {
                    "workload": "stream-refresh",
                    "topology": "collective", "comm": comm,
                    "pods": hier_pods if hier else 0, "bits": cb,
                    "membership": "full", "kernel": "-",
                    "backend": "xla", "polar": svc.plan.polar,
                    "orth": svc.plan.orth,
                    "m": n_dev, "d": d, "r": r, "n_iter": n_iter,
                    "cadence": cadence, "mode": "compiled",
                }
                rec.update(_time_calls(fn, (covs, ref), reps))
                records.append(rec)
                print(
                    f"stream-refresh/{comm} m={n_dev} d={d} r={r} b{cb}: "
                    f"{rec['wall_us']:.1f}us (min {rec['wall_us_min']:.1f})"
                )
            # One query cell per (d, r): the projection is topology- and
            # bits-blind (it never touches the wire).
            if comm == comms[0]:
                qrec = {
                    "workload": "stream-query",
                    "topology": "stacked", "comm": "-", "pods": 0,
                    "bits": 32, "membership": "full", "kernel": "-",
                    "backend": "xla", "polar": "-", "orth": "-",
                    "m": n_dev, "d": d, "r": r, "n_iter": n_iter,
                    "batch": batch, "cadence": cadence, "mode": "compiled",
                }
                qrec.update(_time_calls(svc.query_fn, (queries, ref), reps))
                records.append(qrec)
                print(
                    f"stream-query m={n_dev} d={d} r={r} batch={batch}: "
                    f"{qrec['wall_us']:.1f}us (min {qrec['wall_us_min']:.1f})"
                )
    return {"schema": SCHEMA, "meta": _meta(cadence, batch),
            "records": records}


def _meta(cadence: int, batch: int) -> dict:
    return {
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "device_count": len(jax.devices()),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "bench": "bench_stream",
        "cadence": cadence,
        "batch": batch,
    }


def check(doc: dict, *, max_overhead: float = 4.0) -> tuple:
    """The amortization gate: refresh/cadence vs one query batch.

    For every ``stream-refresh`` cell, the matching ``stream-query`` cell
    is the (m, d, r) one; both sides use ``wall_us_min``.  Returns
    ``(violations, checked)`` — empty list == gate green.
    """
    cadence = doc.get("meta", {}).get("cadence", 1)
    queries = {
        (r["m"], r["d"], r["r"]): r
        for r in doc["records"] if r.get("workload") == "stream-query"
    }
    violations, checked = [], 0
    for rec in doc["records"]:
        if rec.get("workload") != "stream-refresh":
            continue
        q = queries.get((rec["m"], rec["d"], rec["r"]))
        if q is None:
            continue
        checked += 1
        amortized = rec.get("wall_us_min", rec["wall_us"]) / max(cadence, 1)
        budget = max_overhead * q.get("wall_us_min", q["wall_us"])
        if amortized > budget:
            violations.append({
                **{k: rec[k] for k in ("comm", "pods", "bits", "m", "d", "r")},
                "refresh_us_min": rec.get("wall_us_min", rec["wall_us"]),
                "amortized_us": amortized,
                "query_us_min": q.get("wall_us_min", q["wall_us"]),
                "budget_us": budget,
                "cadence": cadence,
            })
    return violations, checked


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_stream.json")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke shapes (seconds on the forced-8-device "
                         "CPU host)")
    ap.add_argument("--comms", default=",".join(DEFAULT_COMMS))
    ap.add_argument("--bits", default=",".join(str(b) for b in DEFAULT_BITS))
    ap.add_argument("--cadence", type=int, default=8,
                    help="observe steps per refresh the gate amortizes over")
    ap.add_argument("--batch", type=int, default=1024,
                    help="query rows per projection call")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--n-iter", type=int, default=1)
    ap.add_argument("--check", default=None, metavar="BENCH_JSON",
                    help="gate an existing sweep instead of recording: "
                         "amortized refresh cost must stay within "
                         "--max-overhead of one query batch per cell")
    ap.add_argument("--max-overhead", type=float, default=4.0,
                    help="allowed ratio of amortized refresh cost to one "
                         "query batch's cost (default 4.0)")
    args = ap.parse_args()

    if args.check:
        doc = load(args.check)
        bad, checked = check(doc, max_overhead=args.max_overhead)
        if bad:
            print(f"# check-stream: {len(bad)} of {checked} cells exceed "
                  f"{args.max_overhead:.1f}x amortized-refresh budget:")
            for v in bad:
                print(f"  {v}")
            raise SystemExit(1)
        print(f"# check-stream: {checked} cells, amortized refresh within "
              f"{args.max_overhead:.1f}x of a query batch everywhere")
        return

    shapes = TINY_SHAPES if args.tiny else DEFAULT_SHAPES
    doc = run_sweep(
        shapes=shapes,
        comms=tuple(args.comms.split(",")),
        bits=tuple(int(b) for b in args.bits.split(",")),
        cadence=args.cadence, batch=args.batch, reps=args.reps,
        n_iter=args.n_iter,
    )
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"# wrote {len(doc['records'])} records -> {args.out}")


if __name__ == "__main__":
    main()
