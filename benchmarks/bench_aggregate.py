"""Aggregation-path benchmark: the repo's recorded perf trajectory.

Sweeps (m, d, r) x backend ("xla" | "pallas") x polar ("svd" |
"newton-schulz") x orth ("qr" | "cholesky-qr2") x layout/comm (below)
through the public aggregation API and writes ``BENCH_aggregate.json`` —
a schema ``benchmarks/run.py`` can pretty-print (``--show-aggregate``),
diff across PRs (``--diff-aggregate old new``), and gate
(``--check-aggregate old new``: >25% machine-calibrated same-mode median
slowdown on any matching cell fails; see ``check``), so every PR leaves a
comparable datapoint.  The (pallas, newton-schulz, cholesky-qr2) cells
are the fused single-launch rounds.

Record layout axes:

  * ``topology`` ("stacked" | "collective") — where the stack lives:
      "stacked"    — ``iterative_refinement`` on a host-stacked (m, d, r)
                     array (what the paper's coordinator runs; exercises
                     the Pallas kernels directly).
      "collective" — ``procrustes_average_collective`` under ``shard_map``
                     over the host mesh's data axis (the production
                     setting; recorded only when more than one device is
                     visible — run under
                     ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
                     to record it on a 1-CPU host, as the CI bench-smoke
                     lane does).
  * ``comm`` — the *communication topology* of a collective cell
      ("psum" | "gather" | "ring" | "hier", the ``repro.comm`` registry;
      "-" on stacked cells, which do no communication).  Since PR 4 this
      is an explicit switch, independent of ``backend``.
  * ``pods`` — the mesh shape of a collective cell: 0 on flat 1-D
      cells; p > 0 on ``comm="hier"`` cells, which run over the 2-D
      (p, m/p) (pod, local) mesh (new in v7; p = m/2 on the CI host).
  * ``bits`` — the *wire precision* of a collective cell's payloads
      (32 | 16 | 8, the ``repro.comm.quantize`` codec registry; stacked
      cells do no communication and always record 32).  Since PR 6 this
      is the fifth explicit switch.
  * ``kernel`` — the round-body fusion of a collective ring cell
      ("-" | "fused-ring"): the (pallas, ring, newton-schulz,
      cholesky-qr2) cell consumes its staged hops inside one pallas_call
      per round (DESIGN.md §3.3, new in v6) — a different program from
      the jnp ring hop loop, so it diffs and gates only against itself.
  * ``workload`` — what the cell times (new in v8): "oneshot" for every
      cell this module records; ``benchmarks.bench_stream`` records the
      streaming service's "stream-refresh" (steady-state refresh: covs
      and previous basis in) and "stream-query" (collective-free batched
      projection) cells into the same schema.

Timing discipline: jit + one warm-up call (compile time recorded
separately), then ``reps`` timed calls each ending in
``block_until_ready``; the record carries the median and spread.  Off-TPU,
``backend="pallas"`` runs the kernels in interpret mode — a correctness
path whose wall time is not comparable to compiled numbers — so each
record carries ``mode: "compiled" | "interpret"`` and the differ refuses to
compare across modes.

Run:  PYTHONPATH=src python -m benchmarks.bench_aggregate \
          [--tiny] [--out BENCH_aggregate.json] [--reps 5] [--n-iter 2]
          [--backends xla,pallas] [--polars svd,newton-schulz]
          [--orths qr,cholesky-qr2] [--comms psum,gather,ring,hier]
          [--bits 32,8] [--shapes 8x1024x16,16x2048x32]
"""

from __future__ import annotations

import argparse
import json
import statistics
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

SCHEMA = "bench_aggregate/v8"
# v1 predates the ``orth=`` switch (upgraded with orth="qr"); v2 predates
# the ``comm`` communication-topology axis (upgraded with the historical
# backend pairing); v3 predates the ``bits`` wire-precision axis
# (upgraded with bits=32 — every pre-v4 cell ran full-precision wires);
# v4 predates the ``membership`` axis (upgraded with "full" — every
# pre-v5 cell ran with all shards alive); v5 predates the ``kernel``
# axis (upgraded with "-" — before v6 every ring cell's hop compute was
# plain jnp; the fused in-kernel ring rounds are new in v6); v6 predates
# the ``pods`` mesh-shape axis (upgraded with 0 — every pre-v7 collective
# cell ran over the flat 1-D data mesh; the hierarchical 2-D cells are
# new in v7); v7 predates the ``workload`` axis (upgraded with "oneshot"
# — every pre-v8 cell timed the one-shot aggregation; the streaming
# service's "stream-refresh" / "stream-query" cells, recorded by
# ``benchmarks.bench_stream``, are new in v8).  ``load`` upgrades all
# seven.
SCHEMA_V1 = "bench_aggregate/v1"
SCHEMA_V2 = "bench_aggregate/v2"
SCHEMA_V3 = "bench_aggregate/v3"
SCHEMA_V4 = "bench_aggregate/v4"
SCHEMA_V5 = "bench_aggregate/v5"
SCHEMA_V6 = "bench_aggregate/v6"
SCHEMA_V7 = "bench_aggregate/v7"

# Record keys that identify a configuration (the diff/check join key).
# ``membership`` keys degraded-mesh cells ("full" | "dead=[k,..]"): a
# masked collective runs a different schedule (survivor-only perm, extra
# resync broadcast on the ring), so its wall time never joins against —
# or gets grouped with — a full-membership cell's.  ``kernel`` keys the
# round-body fusion ("-" | "fused-ring"): the (pallas, ring, NS,
# cholesky-qr2) cell consumes its staged hops inside one pallas_call per
# round (DESIGN.md §3.3) — a different program from the jnp ring, so it
# gates only against itself.  ``pods`` keys the mesh shape of a
# hierarchical cell (0 on every flat-mesh cell; p > 0 means the 2-D
# (p, m/p) mesh of ``comm="hier"``) — a different collective schedule
# per pod count, so each gates only against its own.  ``workload`` keys
# *what* the cell times ("oneshot" | "stream-refresh" | "stream-query",
# new in v8): the streaming service's steady-state refresh (reference
# supplied, covs pre-formed) and its collective-free query projection
# are different programs from the one-shot aggregation, so each diffs
# and gates only against its own kind.
KEY_FIELDS = (
    "workload", "topology", "comm", "pods", "bits", "membership", "kernel",
    "backend", "polar", "orth", "m", "d", "r", "n_iter"
)

DEFAULT_COMMS = ("psum", "gather", "ring", "hier")
DEFAULT_BITS = (32, 8)

DEFAULT_SHAPES = ((8, 1024, 16), (16, 2048, 32), (8, 4096, 64))
TINY_SHAPES = ((4, 128, 4), (2, 96, 8))


def _parse_shapes(spec: str):
    out = []
    for cell in spec.split(","):
        m, d, r = (int(x) for x in cell.lower().split("x"))
        out.append((m, d, r))
    return tuple(out)


def _stack(m: int, d: int, r: int) -> jax.Array:
    key = jax.random.PRNGKey(m * 1_000_003 + d * 1_003 + r)
    return jnp.linalg.qr(jax.random.normal(key, (m, d, r)))[0]


def _time_fn(fn, arg, reps: int) -> Dict[str, float]:
    t0 = time.perf_counter()
    jax.block_until_ready(fn(arg))
    compile_s = time.perf_counter() - t0
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(arg))
        walls.append((time.perf_counter() - t0) * 1e6)
    return {
        "compile_s": compile_s,
        "wall_us": statistics.median(walls),
        "wall_us_min": min(walls),
        "wall_us_max": max(walls),
        "reps": reps,
    }


def _mode(backend: str, comm: str = "-", kernel: str = "-") -> str:
    from repro.kernels.ops import on_tpu

    if backend != "pallas":
        return "compiled"
    if comm == "ring" and kernel == "-":
        # The plain ring schedule's hop compute is jnp (no stacked operand
        # for the kernels to stream — see repro.comm.ring), so off-TPU it
        # still runs compiled, not interpreted.  The fused-ring kernel
        # cell is a pallas_call like any other and interprets off-TPU.
        return "compiled"
    return "compiled" if on_tpu() else "interpret"


def _kernel_cell(backend: str, comm: str, polar: str, orth: str) -> str:
    """The ``kernel`` axis value of a collective cell: "fused-ring" iff
    the cell routes to the in-kernel ring round (repro.core.distributed's
    dispatch rule), "-" otherwise."""
    fused = (
        comm == "ring" and backend == "pallas"
        and polar == "newton-schulz" and orth == "cholesky-qr2"
    )
    return "fused-ring" if fused else "-"


def bench_stacked(shapes, backends, polars, orths, *, n_iter: int, reps: int):
    from repro.core import iterative_refinement

    records = []
    for m, d, r in shapes:
        vs = _stack(m, d, r)
        # Backend innermost: consecutive cells belong to different
        # (topology, comm, backend) gate groups, so a transient noisy-
        # neighbor episode cannot poison a whole group (see ``check``).
        for polar in polars:
            for orth in orths:
                for backend in backends:
                    fn = jax.jit(
                        lambda v, b=backend, p=polar, o=orth:
                        iterative_refinement(
                            v, n_iter, backend=b, polar=p, orth=o
                        )
                    )
                    rec = {
                        "workload": "oneshot",
                        "topology": "stacked", "comm": "-", "pods": 0,
                        "bits": 32,
                        "membership": "full", "kernel": "-",
                        "backend": backend,
                        "polar": polar, "orth": orth,
                        "m": m, "d": d, "r": r, "n_iter": n_iter,
                        "mode": _mode(backend),
                    }
                    rec.update(_time_fn(fn, vs, reps))
                    records.append(rec)
                    print(
                        f"stacked m={m} d={d} r={r} {backend}/{polar}/{orth} "
                        f"[{rec['mode']}]: {rec['wall_us']:.1f}us "
                        f"(compile {rec['compile_s']:.2f}s)"
                    )
    return records


def bench_collective(
    shapes, backends, polars, orths, comms, bits=DEFAULT_BITS,
    *, n_iter: int, reps: int
):
    """The shard_map setting over the host devices (m := device count),
    per registered communication topology (``repro.comm``) and wire
    precision (``repro.comm.quantize``)."""
    from repro.launch.mesh import make_mesh
    from repro.core.distributed import procrustes_average_collective
    from jax.sharding import PartitionSpec as P

    n_dev = len(jax.devices())
    if n_dev < 2:
        print("# collective cells skipped: single-device host "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return []
    mesh = make_mesh((n_dev,), ("data",))
    # The hierarchical lane runs over the 2-D (pods, local) mesh; pod
    # count fixed at n_dev/2 (4 pods of 2 on the forced-8-device CI
    # host) so the inter-pod ring and the intra-pod psum both exist.
    hier_pods = n_dev // 2 if n_dev % 2 == 0 and n_dev >= 4 else 0
    hier_mesh = (
        make_mesh((hier_pods, n_dev // hier_pods), ("pod", "data"))
        if hier_pods else None
    )
    records = []
    for _, d, r in shapes:
        vs = _stack(n_dev, d, r)
        # comm/backend innermost — same decorrelation rationale as
        # ``bench_stacked``: the cells of one gate group are spread across
        # the sweep instead of running back to back.
        for polar in polars:
            for orth in orths:
                for comm in comms:
                    # The plain ring's hop compute ignores backend=
                    # entirely (repro.comm.ring), so sweeping both
                    # backends would time the same compiled program
                    # twice — except the (pallas, NS, cholesky-qr2)
                    # cell, which routes to the fused in-kernel ring
                    # round and is a genuinely different program.
                    hier = comm == "hier"
                    if hier and hier_mesh is None:
                        print(f"# collective/hier cells skipped: "
                              f"{n_dev} devices do not tile into pods")
                        continue
                    if comm == "ring":
                        cell_backends = tuple(
                            b for b in backends
                            if b == "xla"
                            or _kernel_cell(b, comm, polar, orth) != "-"
                        ) or backends[:1]
                    else:
                        cell_backends = backends
                    for backend in cell_backends:
                        for cb in bits:

                            def shard_fn(v, b=backend, p=polar, o=orth,
                                         t=comm, w=cb):
                                out = procrustes_average_collective(
                                    v[0], axis_name="data", n_iter=n_iter,
                                    backend=b, polar=p, orth=o, topology=t,
                                    comm_bits=w,
                                    pod_axis="pod" if t == "hier" else None,
                                )
                                return out[None]

                            fn = jax.jit(
                                jax.shard_map(
                                    shard_fn,
                                    mesh=hier_mesh if hier else mesh,
                                    in_specs=P(
                                        ("pod", "data") if hier else "data",
                                        None, None
                                    ),
                                    out_specs=P(
                                        ("pod", "data") if hier else "data",
                                        None, None
                                    ),
                                    check_vma=False,
                                )
                            )
                            kern = _kernel_cell(backend, comm, polar, orth)
                            rec = {
                                "workload": "oneshot",
                                "topology": "collective", "comm": comm,
                                "pods": hier_pods if hier else 0,
                                "bits": cb, "membership": "full",
                                "kernel": kern,
                                "backend": backend,
                                "polar": polar, "orth": orth, "m": n_dev,
                                "d": d, "r": r,
                                "n_iter": n_iter,
                                "mode": _mode(backend, comm, kern),
                            }
                            rec.update(_time_fn(fn, vs, reps))
                            records.append(rec)
                            pods_tag = f"/p{hier_pods}" if hier else ""
                            print(
                                f"collective/{comm}{pods_tag} m={n_dev} "
                                f"d={d} r={r} "
                                f"{backend}/{polar}/{orth}/b{cb}"
                                f"{'/' + kern if kern != '-' else ''} "
                                f"[{rec['mode']}]: {rec['wall_us']:.1f}us"
                            )
    return records


def run_sweep(
    *, shapes=DEFAULT_SHAPES, backends=("xla", "pallas"),
    polars=("svd", "newton-schulz"), orths=("qr", "cholesky-qr2"),
    comms=DEFAULT_COMMS, bits=DEFAULT_BITS, n_iter: int = 2, reps: int = 5,
) -> dict:
    records = bench_stacked(
        shapes, backends, polars, orths, n_iter=n_iter, reps=reps
    )
    records += bench_collective(
        shapes, backends, polars, orths, comms, bits, n_iter=n_iter,
        reps=reps
    )
    return {
        "schema": SCHEMA,
        "meta": {
            "jax": jax.__version__,
            "platform": jax.default_backend(),
            "device_count": len(jax.devices()),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        },
        "records": records,
    }


# ---------------------------------------------------------------------------
# Loading / pretty-printing / diffing (used by ``benchmarks.run``).


def load(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") == SCHEMA_V1:
        # v1 predates the ``orth=`` switch; every v1 record ran thin QR.
        for rec in doc.get("records", []):
            rec.setdefault("orth", "qr")
        doc["schema"] = SCHEMA_V2
    if doc.get("schema") == SCHEMA_V2:
        # v2 predates the explicit ``comm`` axis: collective cells used the
        # historical backend pairing (gather under pallas, psum under xla);
        # stacked cells do no communication.
        for rec in doc.get("records", []):
            if "comm" not in rec:
                rec["comm"] = (
                    "-" if rec.get("topology") == "stacked"
                    else ("gather" if rec.get("backend") == "pallas"
                          else "psum")
                )
        doc["schema"] = SCHEMA_V3
    if doc.get("schema") == SCHEMA_V3:
        # v3 predates the ``bits`` wire-precision axis: every pre-v4 cell
        # ran full-precision fp32 wires.
        for rec in doc.get("records", []):
            rec.setdefault("bits", 32)
        doc["schema"] = SCHEMA_V4
    if doc.get("schema") == SCHEMA_V4:
        # v4 predates the ``membership`` axis: every pre-v5 cell ran with
        # all shards alive.
        for rec in doc.get("records", []):
            rec.setdefault("membership", "full")
        doc["schema"] = SCHEMA_V5
    if doc.get("schema") == SCHEMA_V5:
        # v5 predates the ``kernel`` round-body-fusion axis: pre-v6 ring
        # cells all ran the plain jnp hop loop (the fused in-kernel ring
        # round did not exist), so every record upgrades to "-".
        for rec in doc.get("records", []):
            rec.setdefault("kernel", "-")
        doc["schema"] = SCHEMA_V6
    if doc.get("schema") == SCHEMA_V6:
        # v6 predates the ``pods`` mesh-shape axis: every pre-v7 cell ran
        # over the flat 1-D data mesh (the hierarchical (pods, local)
        # cells are new in v7), so every record upgrades to 0.
        for rec in doc.get("records", []):
            rec.setdefault("pods", 0)
        doc["schema"] = SCHEMA_V7
    if doc.get("schema") == SCHEMA_V7:
        # v7 predates the ``workload`` axis: every pre-v8 cell timed the
        # one-shot aggregation (the streaming service's refresh/query
        # cells are new in v8), so every record upgrades to "oneshot".
        for rec in doc.get("records", []):
            rec.setdefault("workload", "oneshot")
        doc["schema"] = SCHEMA
    if doc.get("schema") != SCHEMA:
        raise ValueError(
            f"{path}: expected schema {SCHEMA!r}, got {doc.get('schema')!r}"
        )
    return doc


_KEY_DEFAULTS = {
    "membership": "full", "kernel": "-", "pods": 0, "workload": "oneshot",
}


def _key(rec: dict):
    # Tolerate records that predate an axis (load() upgrades files, but
    # in-memory docs may be handed to check()/diff() directly).
    return tuple(rec.get(k, _KEY_DEFAULTS[k]) if k in _KEY_DEFAULTS else rec[k]
                 for k in KEY_FIELDS)


def _fields(rec: dict) -> str:
    """The key columns of one record, CSV — tolerant like ``_key`` so
    pretty-printing/diffing an in-memory doc that predates an axis
    renders its default instead of raising."""
    return ",".join(str(v) for v in _key(rec))


def pretty_print(doc: dict) -> None:
    meta = doc.get("meta", {})
    print(
        f"# {SCHEMA} | jax {meta.get('jax')} on {meta.get('platform')} "
        f"x{meta.get('device_count')} | {meta.get('timestamp')}"
    )
    hdr = KEY_FIELDS + ("mode", "wall_us", "compile_s")
    print(",".join(hdr))
    for rec in sorted(doc["records"], key=_key):
        print(
            f"{_fields(rec)},"
            f"{rec['mode']},{rec['wall_us']:.1f},{rec['compile_s']:.2f}"
        )


def diff(old: dict, new: dict) -> None:
    """Per-configuration wall-time ratio new/old; the PR-over-PR record.

    Refuses cross-platform and cross-mode comparisons: a CPU sweep against
    a TPU sweep (or interpret against compiled) is not a perf trajectory.
    """
    p_old = old.get("meta", {}).get("platform")
    p_new = new.get("meta", {}).get("platform")
    if p_old != p_new:
        raise ValueError(
            f"refusing to diff sweeps from different platforms "
            f"({p_old!r} vs {p_new!r}); wall times are not comparable"
        )
    olds = {_key(r): r for r in old["records"]}
    print(",".join(KEY_FIELDS) + ",old_us,new_us,ratio")
    for rec in sorted(new["records"], key=_key):
        prev = olds.get(_key(rec))
        if prev is None:
            status = "NEW"
        elif prev.get("mode") != rec.get("mode"):
            status = f"MODE {prev.get('mode')}->{rec.get('mode')}"
        else:
            status = f"{rec['wall_us'] / max(prev['wall_us'], 1e-9):.3f}"
        old_us = f"{prev['wall_us']:.1f}" if prev else "-"
        print(f"{_fields(rec)},{old_us},{rec['wall_us']:.1f},{status}")


def check(
    old: dict, new: dict, *, threshold: float = 1.25, calibrate: bool = True,
    cell_threshold: float = 5.0, cell_floor_us: float = 1000.0,
) -> tuple:
    """Same-mode regression gate: the PR-blocking form of ``diff``.

    Joins matching-key cells whose recorded ``mode`` agrees
    (compiled-vs-compiled or interpret-vs-interpret; a mode flip is a path
    change, not a perf regression).  Cross-platform sweeps are refused
    outright, like ``diff``.

    Robustness design — the gate must hold on noisy shared runners:

    * **min-of-reps.**  Per-cell ratios compare ``wall_us_min``, not the
      median: scheduler contention only ever *inflates* a wall time, so
      the minimum is the least-noise estimate of what the path costs.
    * **per-population calibration.**  ``calibrate=True`` divides every
      ratio by the median ratio across the matched cells of the same
      ``topology`` ("stacked" | "collective"): the committed baseline and
      the CI runner are different machines, and machine speed is not a
      regression.  The two populations are calibrated separately because
      they have different noise regimes — the collective cells run a
      multi-process shard_map whose scheduling cost swings together and
      independently of the single-process stacked cells, so a global
      median would misread one population's lucky run as the other's
      regression.  The deliberate blind spot (same class the global
      calibration had): a change slowing every cell of a population by
      the same factor is invisible — run ``calibrate=False`` on
      same-machine sweeps to see it.
    * **group verdicts.**  The primary verdict is per *path group*
      (workload, topology, comm, pods, bits, membership, kernel) — the
      unit a code change actually moves —
      using the median calibrated ratio of the group's cells (backend /
      polar / orth / shape variants).  A noisy-neighbor episode hits a
      few arbitrary cells; a real path regression moves its whole group.
      Backend variants fold into one group since v4: a wire-tier
      regression (a codec suddenly costing an extra pass) shows up on
      every backend of its (comm, bits) cell alike, and folding keeps
      group populations large enough for a meaningful median on the
      tiny CI sweep.  The sweeps interleave groups (bits/backend/comm
      innermost) so one noise episode cannot hit all of a group's cells
      back to back.  Degraded-mesh cells (``membership != "full"``) form
      their own groups: a masked collective runs a genuinely different
      schedule, so the gate never reads a full-vs-masked wall-time gap as
      a regression — the membership-agnosticity contract of the elastic
      runtime (tests/test_elastic.py).
    * **cell blowups.**  Narrow single-cell regressions are still caught,
      at a loose ``cell_threshold`` (default 5x) and only for cells at or
      above ``cell_floor_us`` in both sweeps — sub-millisecond cells
      measure launch jitter, not path cost.

    Returns ``(regressions, checked)``: offending entries (group entries
    carry ``group`` + ``cal_ratio`` + ``cells``; cell entries the record
    fields + ``old_us``/``ratio``/``cal_ratio``) and the number of cells
    compared.  Empty list == gate green.
    """
    p_old = old.get("meta", {}).get("platform")
    p_new = new.get("meta", {}).get("platform")
    if p_old != p_new:
        raise ValueError(
            f"refusing to check sweeps from different platforms "
            f"({p_old!r} vs {p_new!r}); wall times are not comparable"
        )
    olds = {_key(r): r for r in old["records"]}
    matched = []
    for rec in sorted(new["records"], key=_key):
        prev = olds.get(_key(rec))
        if prev is None or prev.get("mode") != rec.get("mode"):
            continue
        t_new = rec.get("wall_us_min", rec["wall_us"])
        t_old = prev.get("wall_us_min", prev["wall_us"])
        matched.append((rec, prev, t_new / max(t_old, 1e-9)))
    by_pop: dict = {}
    for rec, _, ratio in matched:
        by_pop.setdefault(rec["topology"], []).append(ratio)
    norms = {
        pop: (statistics.median(rs) if calibrate and len(rs) >= 2 else 1.0)
        for pop, rs in by_pop.items()
    }
    groups: dict = {}
    for rec, prev, ratio in matched:
        g = (rec.get("workload", "oneshot"), rec["topology"], rec["comm"],
             rec.get("pods", 0), rec.get("bits", 32),
             rec.get("membership", "full"), rec.get("kernel", "-"))
        groups.setdefault(g, []).append(ratio / norms[rec["topology"]])
    regressions = [
        {"group": g, "cal_ratio": statistics.median(rs), "cells": len(rs)}
        for g, rs in sorted(groups.items())
        if statistics.median(rs) > threshold
    ]
    regressions += [
        {**rec, "old_us": prev.get("wall_us_min", prev["wall_us"]),
         "ratio": ratio, "cal_ratio": ratio / norms[rec["topology"]]}
        for rec, prev, ratio in matched
        if ratio / norms[rec["topology"]] > cell_threshold
        and prev.get("wall_us_min", prev["wall_us"]) >= cell_floor_us
        and rec.get("wall_us_min", rec["wall_us"]) >= cell_floor_us
    ]
    return regressions, len(matched)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_aggregate.json")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke shapes (seconds, works in interpret mode)")
    ap.add_argument("--shapes", default=None,
                    help="comma-separated MxDxR cells, e.g. 8x1024x16,16x2048x32")
    ap.add_argument("--backends", default="xla,pallas")
    ap.add_argument("--polars", default="svd,newton-schulz")
    ap.add_argument("--orths", default="qr,cholesky-qr2")
    ap.add_argument("--comms", default=",".join(DEFAULT_COMMS),
                    help="communication topologies for the collective "
                         "cells (repro.comm registry)")
    ap.add_argument("--bits", default=",".join(str(b) for b in DEFAULT_BITS),
                    help="comm_bits wire tiers for the collective cells "
                         "(repro.comm.quantize; stacked cells always "
                         "record 32)")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--n-iter", type=int, default=2)
    args = ap.parse_args()

    shapes = (
        _parse_shapes(args.shapes) if args.shapes
        else (TINY_SHAPES if args.tiny else DEFAULT_SHAPES)
    )
    doc = run_sweep(
        shapes=shapes,
        backends=tuple(args.backends.split(",")),
        polars=tuple(args.polars.split(",")),
        orths=tuple(args.orths.split(",")),
        comms=tuple(args.comms.split(",")),
        bits=tuple(int(b) for b in args.bits.split(",")),
        n_iter=args.n_iter,
        reps=args.reps,
    )
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"# wrote {len(doc['records'])} records -> {args.out}")


if __name__ == "__main__":
    main()
