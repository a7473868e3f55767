"""Closed loop, one caller: distributed PCA jobs back to back.

Set-up makes ``datasets`` row sets on the device from the seed, each
``rows_per_shard`` rows on every one of the traffic's ``shards`` shards
(one shard per chip, so the cell asks for that many chips), and runs
``warmup_jobs`` jobs.  The window calls the program's public entry,
``repro.core.distributed.distributed_pca``, once per job, as a user does,
cycling over the resident row sets, until ``--seconds`` have passed and
the job in flight has ended.  ``pca_job_s`` is the window's length over
the jobs it completed.  The check compares every job's (d, r) basis with
the reference's span of the same rows (``bench/reference.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, List, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import data, reference as ref
from bench.harness import Check, WindowResult


@dataclasses.dataclass
class State:
    mesh: Any
    rows: Tuple[jax.Array, ...]
    system: Any
    outs: List[Tuple[int, jax.Array]] = dataclasses.field(default_factory=list)


def reference_basis(rows: jax.Array, cfg: dict, precision: dict):
    """The reference's (d, r) float64 span of one job's rows: each shard's
    covariance and local span on its own device, then Algorithm 2."""
    shards = sorted(rows.addressable_shards, key=lambda s: s.index[0].start or 0)
    local = []
    for s in shards:
        c = ref.covariance(s.data, precision["covariance"])
        v = ref.leading_span(c, cfg["r"], iters=cfg["solver_iters"],
                             start_key=cfg["solver_start_key"],
                             precision=precision["solve"])
        local.append(ref.to_host(v))
        del c, v
    return ref.procrustes_average(local, cfg["n_iter"])


class Program:
    """The system under test: ``distributed_pca`` as the configuration
    states it, called afresh for every job."""

    def __init__(self, cfg: dict, mesh):
        self.cfg, self.mesh = cfg, mesh

    def job(self, rows):
        from repro.core.distributed import distributed_pca

        c = self.cfg
        return distributed_pca(rows, self.mesh, c["r"], n_iter=c["n_iter"],
                               solver=c["solver"], iters=c["solver_iters"],
                               backend=c["backend"])

    def describe(self) -> str:
        from repro.plan.planner import resolve_plan

        c = self.cfg
        pl = resolve_plan(None, m=self.mesh.size, d=c["d"], r=c["r"],
                          n_iter=c["n_iter"], backend=c["backend"])
        return (f"distributed_pca plan: backend={pl.backend} "
                f"topology={pl.topology} polar={pl.polar} orth={pl.orth} "
                f"comm_bits={pl.comm_bits} m={self.mesh.size}")


class Control:
    """The control: the reference in the program's place, one precision
    step below the configuration's."""

    def __init__(self, cfg: dict):
        self.cfg = cfg

    def job(self, rows):
        return reference_basis(rows, self.cfg, self.cfg["control_precision"])

    def describe(self) -> str:
        return f"control: reference at {self.cfg['control_precision']}"


def _make_rows(key, *, d, r, spectrum, n, count):
    kf, kr = jax.random.split(key)
    factor = data.sampling_factor(kf, d, r, spectrum)
    return tuple(data.sample_gaussian(k, factor, n)
                 for k in jax.random.split(kr, count))


def setup(ctx) -> State:
    from repro.comm import DATA_AXIS
    from repro.launch.mesh import make_mesh

    cfg, mix = ctx.cfg, ctx.traffic
    m = int(mix["shards"])
    if m != ctx.chips:
        raise ValueError(f"the traffic spreads a job's rows over {m} shards, "
                         f"one per chip, and the cell has {ctx.chips} chips")
    mesh = make_mesh((m,), (DATA_AXIS,), devices=ctx.devices)
    sharding = NamedSharding(mesh, P(DATA_AXIS, None))
    count = int(mix["datasets"])
    make = jax.jit(
        functools.partial(_make_rows, d=cfg["d"], r=cfg["r"],
                          spectrum=cfg["spectrum"],
                          n=m * cfg["rows_per_shard"], count=count),
        out_shardings=(sharding,) * count)
    rows = make(data.seed_key(ctx.seed))
    jax.block_until_ready(rows)
    system = Program(cfg, mesh) if ctx.system == "program" else Control(cfg)
    ctx.log(f"[{ctx.workload['name']}] {system.describe()}")
    for i in range(int(mix["warmup_jobs"])):
        jax.block_until_ready(system.job(rows[i % count]))
    return State(mesh=mesh, rows=rows, system=system)


def window(ctx, st: State, seconds: float) -> WindowResult:
    count = len(st.rows)
    ends, phases = [], []
    t0 = time.perf_counter()
    while True:
        k = len(ends) % count
        before = dict(ctx.compiles.by_event)
        with ctx.span("bench.job"):
            v = st.system.job(st.rows[k])
            jax.block_until_ready(v)
        st.outs.append((k, v))
        ends.append(time.perf_counter())
        phases.append({e: d - before.get(e, 0.0)
                       for e, d in ctx.compiles.by_event.items()
                       if d - before.get(e, 0.0) > 0})
        if ends[-1] - t0 >= seconds:
            break
    jobs, t = len(ends), ends[-1]
    each = np.diff([t0] + ends)
    ctx.counters["jobs"] = jobs
    slow = int(np.argmax(each))
    ctx.log(f"[{ctx.workload['name']}] {jobs} jobs; seconds each: min "
            f"{each.min():.4f} median {np.median(each):.4f} max {each.max():.4f}"
            f"; the slowest job's JAX events (s): "
            + ", ".join(f"{e.rsplit('/', 1)[-1]}={d:.4f}"
                        for e, d in phases[slow].items()))
    return WindowResult(e2e={"pca_job_s": (t - t0) / jobs}, attempted=jobs,
                        failed=0, window_s=t - t0)


def check(ctx, st: State, res: WindowResult) -> List[Check]:
    """Every job's basis against the reference span of its rows."""
    cfg = ctx.cfg
    want = {k: reference_basis(st.rows[k], cfg, cfg["precision"])
            for k in sorted({k for k, _ in st.outs})}
    worst = max(ref.sin_theta(ref.to_host(v), want[k]) for k, v in st.outs)
    return [Check("basis_sin_max", worst, cfg["limits"]["basis_sin_max"])]
