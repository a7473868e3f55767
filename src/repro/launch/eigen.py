"""Distributed-PCA job driver (the paper's own workload, role R1).

``python -m repro.launch.eigen --d 512 --r 16 --n-per-shard 2048``

Runs one-shot Procrustes-fixed distributed PCA over the host mesh's data
axis and reports subspace distances vs. the centralized estimator — the
production entry point for the algorithm the paper contributes.

``--plan auto`` hands the five execution knobs (``--backend``,
``--topology``, ``--polar``, ``--orth``, ``--comm-bits``; any
explicitly passed flag stays a pin, and the wire-precision axis is
planned only under an explicit ``--comm-bits auto``) to the cost-model
planner (``repro.plan``); ``--explain`` prints the scored plan table —
every cell's predicted communication words and wire bits (the verified
``repro.comm.comm_cost`` model, byte for byte), FLOPs, and roofline
terms, with the chosen cell marked.  ``--calibrate
BENCH_aggregate.json`` refines the planner's latency/throughput
constants from a recorded sweep on this machine.

``--stream STEPS`` runs the same estimation as a *streaming* job
(``repro.stream``): rows arrive in STEPS per-shard chunks, the service
refreshes on a cadence with the previous basis as the Procrustes
reference, and the report gains stream_* staleness/drift/refresh stats.
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp

from repro.core import (
    central_estimate,
    dist_2,
    distributed_pca,
    empirical_covariance,
    local_bases,
    naive_average,
    procrustes_fix_average,
)
from repro.comm.topology import DATA_AXIS, POD_AXIS
from repro.data import synthetic as syn
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_aggregation_mesh, make_host_mesh

log = logging.getLogger("repro.eigen")


def run(
    d: int = 256,
    r: int = 8,
    n_per_shard: int = 1024,
    *,
    delta: float = 0.2,
    n_iter: int = 2,
    solver: str = "subspace",
    iters: int = 40,
    seed: int = 0,
    mesh=None,
    backend: str | None = None,
    polar: str | None = None,
    orth: str | None = None,
    topology: str | None = None,
    comm_bits=None,
    plan=None,
    explain: bool = False,
    calibration=None,
    fail_at: str | None = None,
    pods: int | None = None,
    stream: int | None = None,
    cadence: int | None = None,
):
    from repro import plan as planlib

    # The hier topology runs over a 2-D (pod, local) mesh; everything
    # else over the host mesh's flat data axis.  The two flags go
    # together so the mesh shape and the schedule can never disagree.
    if (topology == "hier") != (pods is not None):
        raise ValueError(
            "--topology hier and --pods go together (the hierarchical "
            f"schedule needs the 2-D mesh; got topology={topology!r}, "
            f"pods={pods!r})"
        )
    if topology == "hier":
        if fail_at:
            raise ValueError(
                "--fail-at composes with the flat topologies only for now "
                "(the elastic runtime re-plans at the survivor count, "
                "which need not tile into pods)"
            )
        mesh = mesh or make_aggregation_mesh(pods=pods)
        m = mesh.shape[POD_AXIS] * mesh.shape[DATA_AXIS]
    else:
        mesh = mesh or make_host_mesh(model=1)
        m = mesh.shape[DATA_AXIS]
    # One resolution for the whole job: the collective, the shard-local
    # covariance backend, and the printed table all see the same Plan.
    pl = planlib.resolve_plan(
        plan, m=m, d=d, r=r, n_iter=n_iter, backend=backend,
        topology=topology, polar=polar, orth=orth, comm_bits=comm_bits,
        calibration=calibration, pods=pods,
    )
    if explain:
        _, table = planlib.explain(
            m=m, d=d, r=r, n_iter=n_iter, backend=backend,
            topology=topology, polar=polar, orth=orth, comm_bits=comm_bits,
            calibration=calibration, plan=pl, pods=pods,
        )
        print(table)
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    tau = syn.spectrum_m1(d, r, delta=delta)
    sigma, u, factor = syn.covariance_from_spectrum(k1, tau)
    v1 = u[:, :r]
    samples = syn.sample_gaussian(k2, factor, m * n_per_shard)

    report = None
    svc = None
    t0 = time.perf_counter()
    if stream:
        # Streaming lane: the same rows arrive in `stream` per-shard
        # chunks through a SubspaceService (repro.stream) — refreshes on
        # the cadence, previous basis as the alignment reference.  A
        # --fail-at "shard:step" schedule composes: the service adopts
        # the injector's membership each step (elastic refresh on death).
        from repro.stream import SubspaceService

        if n_per_shard % stream:
            raise ValueError(
                f"--stream {stream} must divide --n-per-shard "
                f"{n_per_shard} (fixed-size chunks keep one compiled "
                "update program)"
            )
        injector = None
        if fail_at:
            from repro.runtime.fault import FailureInjector

            injector = FailureInjector(
                fail_at=FailureInjector.parse_fail_spec(fail_at)
            )
        svc = SubspaceService(
            mesh, d, r, n_iter=n_iter,
            cadence=cadence or max(stream // 4, 1),
            solver=solver, iters=iters, plan=pl, calibration=calibration,
        )
        chunk = n_per_shard // stream
        xs3 = samples.reshape(m, n_per_shard, d)
        for t in range(stream):
            if injector is not None:
                svc.set_membership(injector.membership_at(t, m))
            svc.observe(xs3[:, t * chunk:(t + 1) * chunk, :])
        if svc.stats["staleness"]:
            svc.refresh()  # serve the full-data basis before reporting
        v_dist = svc.basis
    elif fail_at:
        # Elastic lane: a "shard:round,shard:round" kill schedule runs the
        # same estimation through repro.runtime.elastic — dead shards are
        # masked out of the collectives round by round, each membership
        # change re-plans at the survivor count.
        from repro.runtime.elastic import elastic_pca
        from repro.runtime.fault import FailureInjector

        injector = FailureInjector(
            fail_at=FailureInjector.parse_fail_spec(fail_at)
        )
        report = elastic_pca(
            samples, mesh, r, n_iter=n_iter, solver=solver, iters=iters,
            plan=pl, injector=injector, calibration=calibration,
        )
        v_dist = report.basis
    else:
        v_dist = distributed_pca(
            samples, mesh, r, n_iter=n_iter, solver=solver, iters=iters,
            plan=pl,
        )
    v_dist.block_until_ready()
    t_dist = time.perf_counter() - t0

    # The references use the job's own eigensolver: a dense eigh at the
    # paper_pca width (d=8192) takes many minutes to compile for a TPU.
    xs = samples.reshape(m, n_per_shard, d)
    covs = jax.vmap(lambda x: empirical_covariance(x))(xs)
    v_cent, _ = central_estimate(covs, r, method=solver, iters=iters)
    vs = local_bases(covs, r, method=solver, iters=iters)
    stats = {
        "m": m,
        "n": n_per_shard,
        "d": d,
        "r": r,
        # The *resolved* execution plan (what actually ran).
        "backend": pl.backend,
        "polar": pl.polar,
        "orth": pl.orth,
        "topology": pl.topology,
        "pods": pl.pods,
        "ring_chunk": pl.ring_chunk,
        "comm_bits": pl.comm_bits,
        "plan_source": pl.source,
        "predicted_words": pl.words,
        "predicted_bits": pl.bits,
        "dist_aligned": float(dist_2(v_dist, v1)),
        "dist_central": float(dist_2(v_cent, v1)),
        "dist_naive": float(dist_2(naive_average(vs), v1)),
        "dist_local0": float(dist_2(vs[0], v1)),
        "wall_s": t_dist,
    }
    if svc is not None:
        s = svc.stats
        stats["stream_steps"] = s["step"]
        stats["stream_rows_seen"] = s["rows_seen"]
        stats["stream_refreshes"] = s["refreshes"]
        stats["stream_cadence"] = s["cadence"]
        stats["stream_staleness"] = s["staleness"]
        stats["stream_last_jump"] = s["last_jump"]
        stats["stream_drift"] = svc.drift()
        stats["replans"] = s["replans"]
        if s["events"]:
            stats["events"] = s["events"]
    if report is not None:
        stats["replans"] = report.replans
        stats["final_m_active"] = report.final_membership.m_active
        stats["events"] = [
            f"round {e.round_index}: {e.reason} "
            f"(m'={e.membership.m_active}, dead={list(e.membership.dead)}, "
            f"plan={e.plan.topology}/{e.plan.comm_bits})"
            for e in report.events
        ]
    return v_dist, stats


def main():
    from repro.plan import (
        BACKEND_CHOICES,
        COMM_BITS_CHOICES,
        ORTH_CHOICES,
        PLAN_CHOICES,
        POLAR_CHOICES,
        TOPOLOGY_CHOICES,
        load_calibration,
    )

    ap = argparse.ArgumentParser()
    ap.add_argument("--d", type=int, default=256)
    ap.add_argument("--r", type=int, default=8)
    ap.add_argument("--n-per-shard", type=int, default=1024)
    ap.add_argument("--n-iter", type=int, default=2)
    ap.add_argument("--solver", default="subspace", choices=["subspace", "eigh"])
    ap.add_argument("--backend", default="auto", choices=BACKEND_CHOICES,
                    help="aggregation path: pure XLA, Pallas kernels, or "
                         "auto (kernels on TPU)")
    ap.add_argument("--polar", default=None, choices=POLAR_CHOICES,
                    help="r x r polar factor: closed-form SVD or the "
                         "matmul-only Newton-Schulz iteration (fused "
                         "in-kernel on the pallas backend); default svd, "
                         "or planner-chosen under --plan auto")
    ap.add_argument("--orth", default=None, choices=ORTH_CHOICES,
                    help="per-round orthonormalization: thin Householder "
                         "QR or CholeskyQR2 (with --backend pallas "
                         "--polar newton-schulz the whole round fuses "
                         "into a single kernel launch); default qr, or "
                         "planner-chosen under --plan auto")
    ap.add_argument("--topology", default="auto", choices=TOPOLOGY_CHOICES,
                    help="communication schedule of the aggregation "
                         "(repro.comm): psum all-reduces, coordinator "
                         "all-gather, or the overlapped ring (with "
                         "--backend pallas --polar newton-schulz --orth "
                         "cholesky-qr2 the ring hops fuse into the "
                         "one-launch kernel round); 'hier' is the "
                         "two-level (pod, local) schedule and needs "
                         "--pods; auto keeps the historical backend "
                         "pairing (or defers to the planner under "
                         "--plan auto)")
    ap.add_argument("--pods", type=int, default=None,
                    help="pod count of the 2-D (pods, m/pods) aggregation "
                         "mesh for --topology hier: intra-pod psum on the "
                         "fast link, a p-hop ring on the slow inter-pod "
                         "link (quantized by --comm-bits; intra stays f32)")
    ap.add_argument("--comm-bits", default=None, choices=COMM_BITS_CHOICES,
                    help="wire precision of the aggregation collectives "
                         "(repro.comm.quantize): 32 exact, 16 bf16 cast, "
                         "8 stochastic int8 with per-column scales and "
                         "error feedback; 'auto' lets the planner trade "
                         "precision against bandwidth; default 32")
    ap.add_argument("--plan", default="none", choices=PLAN_CHOICES,
                    help="'auto': score every (backend x topology x polar "
                         "x orth x comm_bits) cell with the repro.plan "
                         "cost model and run the cheapest (explicit knob "
                         "flags act as pins; comm_bits stays pinned at 32 "
                         "unless --comm-bits auto); 'none': legacy "
                         "per-knob resolution")
    ap.add_argument("--explain", action="store_true",
                    help="print the scored plan table (predicted words / "
                         "flops / roofline terms per cell, chosen cell "
                         "marked) before running")
    ap.add_argument("--calibrate", default=None, metavar="BENCH_JSON",
                    help="refine the planner's constants from a recorded "
                         "bench_aggregate sweep (e.g. BENCH_aggregate.json); "
                         "only consulted when the planner runs, i.e. with "
                         "--plan auto (or --polar/--orth auto)")
    ap.add_argument("--fail-at", default=None, metavar="SHARD:ROUND[,..]",
                    help="elastic fault injection: kill shard k before "
                         "refinement round t (e.g. '2:1', or '2:1,5:3'); "
                         "the run completes over the survivors, re-planning "
                         "the collective at the reduced shard count "
                         "(repro.runtime.elastic); with --stream, t counts "
                         "observe steps and the service refreshes "
                         "elastically on the death")
    ap.add_argument("--stream", type=int, default=None, metavar="STEPS",
                    help="streaming lane (repro.stream): feed the same "
                         "rows in STEPS per-shard chunks through a "
                         "SubspaceService — cadence-triggered Procrustes "
                         "refreshes with the previous basis as reference — "
                         "and report the served basis plus stream_* stats")
    ap.add_argument("--cadence", type=int, default=None,
                    help="refresh every CADENCE observe steps in the "
                         "--stream lane (default: STEPS // 4)")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    use_compile_cache()
    plan = "auto" if args.plan == "auto" else None
    # Under --plan auto, unspecified/"auto" flags are free axes the
    # planner decides; an explicitly passed concrete flag is a pin.
    cal = load_calibration(args.calibrate) if args.calibrate else None
    _, stats = run(
        args.d, args.r, args.n_per_shard, n_iter=args.n_iter,
        solver=args.solver, backend=args.backend, polar=args.polar,
        orth=args.orth, topology=args.topology, comm_bits=args.comm_bits,
        plan=plan, explain=args.explain, calibration=cal,
        fail_at=args.fail_at, pods=args.pods,
        stream=args.stream, cadence=args.cadence,
    )
    for k, v in stats.items():
        print(f"{k}: {v}")


if __name__ == "__main__":
    main()
