"""Closed loop, one caller: genotype PCA jobs back to back.

Set-up makes ``datasets`` sets of int8 genotypes on the device from the
seed (``bench/genotype_data.py``; every set has ``shards`` sites of
``rows_per_shard`` participants, one site per chip) and runs
``warmup_jobs`` jobs.  The window is ``job_loop``'s: it calls
``repro.core.distributed.distributed_pca`` once per job, with the
configuration's standardization, cycling over the resident sets, until
``--seconds`` have passed and the job in flight has ended;
``pca_job_s`` is the window's length over the jobs it completed.  The
check compares every job's (d, r) basis with the plain reference's span
of its set (``bench/reference_genotype.py``).

A program whose ``distributed_pca`` takes no ``standardize`` cannot run
this cell: set-up says so before it makes any data.
"""

from __future__ import annotations

import functools
import inspect
from typing import List

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import data, genotype_data
from bench import reference as ref
from bench import reference_genotype as rg
from bench.drivers import job_loop
from bench.harness import Check, WindowResult


class Program:
    """The system under test: ``distributed_pca`` on genotype counts under
    the configuration's standardization, called afresh for every job."""

    def __init__(self, cfg: dict, mesh):
        from repro.core import distributed

        try:
            inspect.signature(distributed.distributed_pca).bind_partial(
                standardize=cfg["standardize"])
        except TypeError as e:
            raise TypeError(
                "this program's distributed_pca takes no standardize=, so "
                f"it cannot run genotype counts ({e})") from None
        self.cfg, self.mesh = cfg, mesh

    def job(self, counts):
        from repro.core.distributed import distributed_pca

        c = self.cfg
        return distributed_pca(counts, self.mesh, c["r"], n_iter=c["n_iter"],
                               solver=c["solver"], iters=c["solver_iters"],
                               backend=c["backend"],
                               standardize=c["standardize"])

    def describe(self) -> str:
        from repro.core.distributed import _device_bytes, _local_form
        from repro.plan.planner import resolve_plan

        c = self.cfg
        pl = resolve_plan(None, m=self.mesh.size, d=c["d"], r=c["r"],
                          n_iter=c["n_iter"], backend=c["backend"])
        limit = _device_bytes(self.mesh.devices.flat[0])
        return (f"distributed_pca plan: backend={pl.backend} "
                f"topology={pl.topology} polar={pl.polar} orth={pl.orth} "
                f"comm_bits={pl.comm_bits} m={self.mesh.size}; local form "
                f"{_local_form(c['d'], limit)}")


class Control:
    """The control: the reference in the program's place, at the
    configuration's ``control_precision`` (below its ``precision``)."""

    def __init__(self, cfg: dict):
        self.cfg = cfg

    def job(self, counts):
        return rg.reference_basis(counts, self.cfg,
                                  self.cfg["control_precision"])

    def describe(self) -> str:
        return f"control: reference at {self.cfg['control_precision']}"


def setup(ctx) -> job_loop.State:
    from repro.comm import DATA_AXIS
    from repro.launch.mesh import make_mesh

    cfg, mix = ctx.cfg, ctx.traffic
    m = int(mix["shards"])
    if m != ctx.chips:
        raise ValueError(f"the traffic spreads a job's sites over {m} "
                         f"shards, one per chip, and the cell has "
                         f"{ctx.chips} chips")
    mesh = make_mesh((m,), (DATA_AXIS,), devices=ctx.devices)
    system = Program(cfg, mesh) if ctx.system == "program" else Control(cfg)
    ctx.log(f"[{ctx.workload['name']}] {system.describe()}")
    count = int(mix["datasets"])
    sharding = NamedSharding(mesh, P(DATA_AXIS, None))
    make = jax.jit(functools.partial(genotype_data.sites, cfg=cfg,
                                     count=count, shards=m),
                   out_shardings=(sharding,) * count)
    rows = make(data.seed_key(ctx.seed))
    jax.block_until_ready(rows)
    for i in range(int(mix["warmup_jobs"])):
        jax.block_until_ready(system.job(rows[i % count]))
    return job_loop.State(mesh=mesh, rows=rows, system=system)


def window(ctx, st: job_loop.State, seconds: float) -> WindowResult:
    return job_loop.window(ctx, st, seconds)


def check(ctx, st: job_loop.State, res: WindowResult) -> List[Check]:
    """Every job's basis against the reference span of its set."""
    cfg = ctx.cfg
    want = {k: rg.reference_basis(st.rows[k], cfg, cfg["precision"])
            for k in sorted({k for k, _ in st.outs})}
    worst = max(ref.sin_theta(ref.to_host(v), want[k]) for k, v in st.outs)
    return [Check("basis_sin_max", worst, cfg["limits"]["basis_sin_max"])]
