import os
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={os.environ.get('REPRO_DRYRUN_DEVICES', '512')} " + os.environ.get("XLA_FLAGS", "")
os.environ["JAX_PLATFORMS"] = "cpu"  # placeholder devices; never the chip

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, record memory/cost analysis + collective bytes.

MUST be run as a script / module entry (`python -m repro.launch.dryrun`):
the environment lines above execute before any jax import, pinning the CPU
platform and giving it 512 placeholder devices so `jax.make_mesh((2,16,16))` can build the
production mesh.  Nothing is allocated: inputs and parameters are
ShapeDtypeStructs end to end.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  python -m repro.launch.dryrun --all --out artifacts/dryrun
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k --eigen
  python -m repro.launch.dryrun --paper-pca
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.sharding import (
    batch_shardings,
    cache_shardings,
    param_shardings,
    replicated,
)
from repro.launch.specs import decode_state_specs, train_input_specs
from repro.launch.steps import (
    jit_train_step,
    jit_decode_step,
    jit_eigen_steps,
    eigen_opt_init,
)
from repro.models import SHAPES, abstract_params, active_param_count, supports_shape
from repro.optim import AdamWConfig
from repro.optim.adamw import adamw_init
from repro.optim.eigen_compress import EigenCompressConfig
from repro.optim.schedule import warmup_cosine


def _mesh_for(
    multi_pod: bool,
    device_count: Optional[int] = None,
    mesh_shape: Optional[tuple] = None,
):
    n = len(jax.devices())
    if mesh_shape is not None:
        # §Perf lever: alternate factorisation of the same chip count
        # (e.g. 32x8 for llama3.2's 24 heads).
        axes = ("pod", "data", "model") if len(mesh_shape) == 3 else ("data", "model")
        return make_mesh(tuple(mesh_shape), axes)
    if n != 512:
        # reduced meshes for CI smoke (same axis structure; set
        # REPRO_DRYRUN_DEVICES before launching to shrink the placeholder
        # device count)
        if multi_pod:
            assert n >= 8, "multi-pod smoke needs >= 8 devices"
            return make_mesh((2, 2, n // 4), ("pod", "data", "model"))
        return make_mesh((2, n // 2), ("data", "model"))
    return make_production_mesh(multi_pod=multi_pod)


def _analyze(lowered, compiled, chips, t_lower, t_compile) -> Dict[str, Any]:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # some backends return a list
        cost = cost[0]
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", None
            ),
            "alias_size_bytes": getattr(mem, "alias_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_d = {"error": str(e)}
    hlo = compiled.as_text()
    terms = H.roofline(cost, hlo, chips)
    return {
        "memory_analysis": mem_d,
        "flops_per_device": terms.flops,
        "hbm_bytes_per_device": terms.hbm_bytes,
        "collective_bytes_per_device": terms.coll_bytes,
        "collective_breakdown": terms.coll_breakdown,
        "compute_s": terms.compute_s,
        "memory_s": terms.memory_s,
        "collective_s": terms.collective_s,
        "bottleneck": terms.bottleneck,
        "lower_s": t_lower,
        "compile_s": t_compile,
    }


def _lower_cell(cfg, shape, mesh, eigen: bool):
    """Lower one cell's step function; returns (lowered, model_flops)."""
    values_like, axes = abstract_params(cfg)
    if shape.kind in ("prefill", "decode"):
        # Serving convention: inference checkpoints are bf16 — halves every
        # weight all-gather and HBM read on the serve path (§Perf B3).
        values_like = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
            if v.dtype == jnp.float32
            else v,
            values_like,
        )
    if shape.kind == "train":
        batch_like = train_input_specs(cfg, shape)
        if eigen:
            ecfg = EigenCompressConfig()
            train_jit, _, _ = jit_eigen_steps(
                cfg, mesh, values_like, axes, batch_like,
                adamw_cfg=AdamWConfig(),
                schedule=warmup_cosine(3e-4, 100, 10000),
                ecfg=ecfg,
            )
            n_data = 1
            for a in ("pod", "data"):
                if a in mesh.axis_names:
                    n_data *= mesh.shape[a]
            opt_like = jax.eval_shape(
                lambda v: eigen_opt_init(v, ecfg, n_data, axes), values_like
            )
            lowered = train_jit.lower(values_like, opt_like, batch_like)
        else:
            step_jit, _ = jit_train_step(
                cfg, mesh, values_like, axes, batch_like,
                adamw_cfg=AdamWConfig(),
                schedule=warmup_cosine(3e-4, 100, 10000),
            )
            opt_like = jax.eval_shape(adamw_init, values_like)
            lowered = step_jit.lower(values_like, opt_like, batch_like)
        mf = H.model_flops(active_param_count(cfg), shape.tokens, "train")
    elif shape.kind == "prefill":
        from repro.launch.steps import make_prefill_step

        batch_like = train_input_specs(cfg, shape)
        batch_like.pop("labels")
        fn = make_prefill_step(cfg, mesh)
        ps = param_shardings(values_like, axes, mesh, cfg)
        bs = batch_shardings(batch_like, mesh)
        jitted = jax.jit(fn, in_shardings=(ps, bs))
        lowered = jitted.lower(values_like, batch_like)
        mf = H.model_flops(active_param_count(cfg), shape.tokens, "prefill")
    else:  # decode
        tokens_like, cache_like, pos_like = decode_state_specs(cfg, shape)
        jitted, _ = jit_decode_step(cfg, mesh, values_like, axes, cache_like)
        lowered = jitted.lower(values_like, tokens_like, cache_like, pos_like)
        mf = H.model_flops(active_param_count(cfg), shape.global_batch, "decode")
    return lowered, mf


def _cost_of(cfg, shape, mesh, eigen):
    """Compile an (unrolled) config and return per-device cost numbers."""
    lowered, _ = _lower_cell(cfg, shape, mesh, eigen)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = H.collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "hbm": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
    }


def dryrun_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    eigen: bool = False,
    device_count: Optional[int] = None,
    verbose: bool = True,
    accounting: str = "extrapolate",  # extrapolate | unrolled | scan-only
    overrides: Optional[Dict[str, Any]] = None,
    mesh_shape: Optional[tuple] = None,
) -> Dict[str, Any]:
    import dataclasses

    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, why = supports_shape(cfg, shape)
    record: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "eigen": eigen,
        "kind": shape.kind,
        "overrides": overrides or {},
    }
    if not ok:
        record["skipped"] = why
        if verbose:
            print(f"[dryrun] {arch} x {shape_name}: SKIP ({why})")
        return record

    mesh = _mesh_for(multi_pod, device_count, mesh_shape)
    chips = mesh.size
    record["mesh"] = {"shape": list(mesh.shape.values()), "axes": list(mesh.axis_names)}

    from repro.launch.mesh import data_axes
    from repro.models.sharding_ctx import activation_sharding

    with mesh, activation_sharding(mesh, data_axes(mesh)):
        # 1. The PROOF + memory analysis: lower & compile the production
        #    (scanned) graph for the full config.
        t0 = time.time()
        lowered, mf = _lower_cell(cfg, shape, mesh, eigen)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        record.update(_analyze(lowered, compiled, chips, t_lower, t_compile))

        # 2. COST ACCOUNTING: XLA's cost analysis counts while-loop bodies
        #    once, so the scanned numbers under-report per-step cost.
        p_len = max(len(cfg.block_pattern), 1)
        small = cfg.num_layers <= 2 * p_len or cfg.is_encoder_decoder
        if accounting == "unrolled" or (accounting == "extrapolate" and small):
            # exact: fully unrolled graph (kept for small stacks + validation)
            cfg_u = dataclasses.replace(cfg, scan_layers=False)
            c = _cost_of(cfg_u, shape, mesh, eigen)
            record["accounting"] = "unrolled"
            flops, hbm, coll = c["flops"], c["hbm"], c["coll"]
        elif accounting == "extrapolate":
            # linear-in-depth extrapolation from 1-rep and 2-rep unrolled
            # graphs: exact for per-stage-homogeneous stacks (all of ours);
            # validated against a full unroll in EXPERIMENTS.md §Dry-run.
            cfg1 = dataclasses.replace(
                cfg, num_layers=p_len, scan_layers=False
            )
            cfg2 = dataclasses.replace(
                cfg, num_layers=2 * p_len, scan_layers=False
            )
            c1 = _cost_of(cfg1, shape, mesh, eigen)
            c2 = _cost_of(cfg2, shape, mesh, eigen)
            scale = (cfg.num_layers - p_len) / p_len
            flops = c1["flops"] + (c2["flops"] - c1["flops"]) * scale
            hbm = c1["hbm"] + (c2["hbm"] - c1["hbm"]) * scale
            coll = {
                k: c1["coll"][k] + (c2["coll"][k] - c1["coll"][k]) * scale
                for k in c1["coll"]
            }
            record["accounting"] = "extrapolate(1rep,2rep)"
        else:
            record["accounting"] = "scan-only (cost underreported)"
            flops, hbm = record["flops_per_device"], record["hbm_bytes_per_device"]
            coll = record["collective_breakdown"]

        if accounting != "scan-only":
            record["flops_per_device"] = flops
            record["hbm_bytes_per_device"] = hbm
            record["collective_bytes_per_device"] = float(sum(coll.values()))
            record["collective_breakdown"] = coll
            record["compute_s"] = flops / H.PEAK_FLOPS
            record["memory_s"] = hbm / H.HBM_BW
            record["collective_s"] = sum(coll.values()) / H.ICI_BW
            terms = {
                "compute": record["compute_s"],
                "memory": record["memory_s"],
                "collective": record["collective_s"],
            }
            record["bottleneck"] = max(terms, key=terms.get)

    record["model_flops_global"] = mf
    record["model_flops_per_device"] = mf / chips
    useful = mf / chips / max(record["flops_per_device"], 1.0)
    record["useful_flops_ratio"] = useful
    if verbose:
        ma = record["memory_analysis"]
        print(
            f"[dryrun] {arch} x {shape_name} (multi_pod={multi_pod}, eigen={eigen}): "
            f"OK chips={chips} lower={t_lower:.1f}s compile={t_compile:.1f}s"
        )
        print(
            f"  memory_analysis: args={_gb(ma.get('argument_size_bytes'))} "
            f"out={_gb(ma.get('output_size_bytes'))} temp={_gb(ma.get('temp_size_bytes'))}"
        )
        print(
            f"  per-device: flops={record['flops_per_device']:.3e} "
            f"hbm={record['hbm_bytes_per_device']:.3e}B "
            f"coll={record['collective_bytes_per_device']:.3e}B"
        )
        print(
            f"  roofline: compute={record['compute_s']*1e3:.2f}ms "
            f"memory={record['memory_s']*1e3:.2f}ms "
            f"collective={record['collective_s']*1e3:.2f}ms "
            f"-> {record['bottleneck']}-bound; useful={useful:.2%}"
        )
    return record


def _gb(x):
    return f"{x/2**30:.2f}GiB" if isinstance(x, (int, float)) and x else "n/a"


def dryrun_paper_pca(
    *, multi_pod: bool = False, device_count=None, verbose=True,
    backend: Optional[str] = None, polar: Optional[str] = None,
    orth: Optional[str] = None, topology: Optional[str] = None,
    comm_bits=None, plan=None, explain: bool = False, calibration=None,
    plan_device: Optional[str] = None, drop_shards: Optional[str] = None,
    pods: Optional[int] = None, stream_steps: Optional[int] = None,
):
    """Dry-run the paper's own workload (distributed PCA, Algorithm 2).

    ``backend`` selects the compute path ("xla" | "pallas" | "auto") and
    ``topology`` the communication schedule ("psum" | "gather" | "ring" |
    "auto", see ``repro.comm``); ``comm_bits`` the wire precision of its
    payloads (32 | 16 | 8 | "auto").  The collective-bytes accounting
    shows the topology and precision trades directly, and the record
    carries the analytic bits-per-round prediction from
    ``repro.comm.comm_cost`` next to the measured HLO breakdown.  ``polar`` selects the r x r rotation method
    ("svd" | "newton-schulz"); with "newton-schulz" the lowered graph is
    SVD-free, which the HLO accounting reflects.  ``orth`` selects the
    per-round orthonormalization ("qr" | "cholesky-qr2"); the SVD- and
    Householder-free cell is (pallas, newton-schulz, cholesky-qr2).

    ``plan=None|"auto"|Plan`` resolves all four through the execution
    planner (``repro.plan``); ``explain=True`` prints the scored plan
    table for the job's (m, d, r) before lowering.  The record carries
    the resolved plan and its prediction either way.  ``plan_device``
    sets which device model the planner scores against (e.g. ``"tpu"``
    to plan for the v5e target this harness's roofline prices); the
    default is the host device so the planned cell's lowered graph keeps
    well-defined XLA cost analysis (planning pallas cells on a non-TPU
    host lowers them in interpret mode, whose ``pallas_call`` is opaque
    to ``cost_analysis()`` — DESIGN.md §7).

    ``drop_shards`` ("2,5") lowers the *degraded-mesh* program: the
    listed data-axis shards are masked dead (``repro.comm.Membership``),
    the planner prices the survivor count, and the cost-model prediction
    carries the masked wire (the ring genuinely compiles fewer hops —
    the measured HLO breakdown shows it next to the prediction).

    ``topology="hier"`` needs a mesh with a 'pod' axis — either
    ``multi_pod=True`` (the production shape) or an explicit ``pods=p``
    (a bare (p, n/p) aggregation mesh over the placeholder devices).
    The aggregation then spans pod x data machines, the record carries
    the two-level (intra/inter) byte prediction, and ``drop_shards``
    indexes the flattened pod-major machine axis — so a whole-pod drop
    exercises the ring-skips-the-pod path.

    ``stream_steps=N`` lowers the *streaming service* programs instead of
    the one-shot job (``repro.stream``): the steady-state refresh (the
    reference is supplied, so the prediction is ``comm_cost`` with
    ``ref_broadcast=False``, amortized over the N-step cadence) and the
    query path, whose measured collective bytes the record carries —
    zero, by construction, for the replicated-matmul projection.
    """
    from repro import plan as planlib
    from repro.comm import DATA_AXIS, POD_AXIS, Membership, comm_cost
    from repro.configs.paper_pca import CONFIG as pcfg
    from repro.core.distributed import distributed_pca

    if pods:
        n = len(jax.devices())
        if n % int(pods):
            raise ValueError(f"--pods {pods} does not tile {n} devices")
        mesh = make_mesh((int(pods), n // int(pods)), (POD_AXIS, DATA_AXIS))
    else:
        mesh = _mesh_for(multi_pod, device_count)
    chips = mesh.size
    n_data = mesh.shape["data"] * (mesh.shape.get("pod", 1))
    hier = topology == "hier" or (
        isinstance(plan, planlib.Plan) and plan.topology == "hier"
    )
    if hier:
        if POD_AXIS not in mesh.axis_names:
            raise ValueError(
                "--topology hier needs --multi-pod (a mesh with a "
                f"{POD_AXIS!r} axis)"
            )
        # The hier aggregation spans both axes (pod-major machine order).
        agg_pods = mesh.shape[POD_AXIS]
        m_agg = agg_pods * mesh.shape[DATA_AXIS]
    else:
        # Flat collectives run over the "data" axis only.
        agg_pods = None
        m_agg = mesh.shape[DATA_AXIS]
    mem = None
    if drop_shards:
        mem = Membership.from_dead(
            m_agg, (int(s) for s in drop_shards.split(",") if s.strip())
        )
    pl = planlib.resolve_plan(
        plan, m=m_agg, d=pcfg.d, r=pcfg.r, n_iter=pcfg.n_iter,
        backend=backend, topology=topology, polar=polar, orth=orth,
        comm_bits=comm_bits, calibration=calibration,
        device_kind=plan_device, membership=mem, pods=agg_pods,
    )
    if explain:
        _, table = planlib.explain(
            m=m_agg, d=pcfg.d, r=pcfg.r, n_iter=pcfg.n_iter,
            backend=backend, topology=topology, polar=polar, orth=orth,
            comm_bits=comm_bits, calibration=calibration, plan=pl,
            device_kind=plan_device, pods=agg_pods,
        )
        print(table)
    topo = pl.topology
    cost = comm_cost(topo, m=m_agg, d=pcfg.d, r=pcfg.r, n_iter=pcfg.n_iter,
                     comm_bits=pl.comm_bits, membership=mem,
                     pods=agg_pods if topo == "hier" else None)
    samples_like = jax.ShapeDtypeStruct(
        (n_data * pcfg.n_per_shard, pcfg.d), jnp.float32
    )
    record = {
        "arch": "paper-pca",
        "shape": f"d{pcfg.d}_r{pcfg.r}_n{pcfg.n_per_shard}",
        "multi_pod": multi_pod,
        "kind": "eigen",
        "backend": pl.backend,
        "polar": pl.polar,
        "orth": pl.orth,
        "topology": topo,
        "pods": pl.pods,
        "comm_bits": pl.comm_bits,
        "plan_source": pl.source,
        "membership": "full" if mem is None else f"dead={list(mem.dead)}",
        "m_active": m_agg if mem is None else mem.m_active,
        "predicted_collective_words": cost.words,
        "predicted_collective_bits": cost.bits,
        # Wire bytes at the plan's comm_bits tier; directly comparable to
        # the aggregation's share of ``collective_breakdown`` below.
        "predicted_collective_bytes": {
            k: v for k, v in cost.hlo_bytes.items() if v
        },
        "mesh": {"shape": list(mesh.shape.values()), "axes": list(mesh.axis_names)},
    }
    if cost.level_bytes is not None:
        # Two-level schedule: the per-link split the planner priced
        # (the inter level's collective-permute entry is the slow-link
        # hop bill, directly HLO-verifiable).
        record["predicted_collective_bytes_by_level"] = {
            lv: {k: v for k, v in kinds.items() if v}
            for lv, kinds in cost.level_bytes.items()
        }
    if stream_steps:
        # Streaming-service lane: the steady-state refresh program (covs
        # and previous basis in, next basis out; of a refresh cut into
        # slices, the last slice, which holds the aggregation and so every
        # collective) plus the query program.
        from repro.stream import SubspaceService

        svc = SubspaceService(
            mesh, pcfg.d, pcfg.r, n_iter=pcfg.n_iter, cadence=stream_steps,
            solver=pcfg.solver, iters=pcfg.solver_iters, plan=pl,
            membership=mem, device_kind=plan_device,
        )
        s_cost = comm_cost(
            topo, m=m_agg, d=pcfg.d, r=pcfg.r, n_iter=pcfg.n_iter,
            comm_bits=pl.comm_bits, membership=mem, ref_broadcast=False,
            pods=agg_pods if topo == "hier" else None,
        )
        record["kind"] = "eigen-stream"
        record["stream_steps"] = stream_steps
        record["predicted_collective_words"] = s_cost.words
        record["predicted_collective_bits"] = s_cost.bits
        record["predicted_collective_bytes"] = {
            k: v for k, v in s_cost.hlo_bytes.items() if v
        }
        record["predicted_refresh_bits_per_step"] = s_cost.bits / stream_steps
        covs_like = jax.ShapeDtypeStruct((m_agg, pcfg.d, pcfg.d), jnp.float32)
        ref_like = jax.ShapeDtypeStruct((pcfg.d, pcfg.r), jnp.float32)
        programs = svc.refresh_programs(with_ref=True)
        # The last slice of several also takes the running bases.
        basis_like = (
            (jax.ShapeDtypeStruct((m_agg, pcfg.d, pcfg.r), jnp.float32),)
            if len(programs) > 1 else ()
        )
        record["refresh_slices"] = len(programs)
        t0 = time.time()
        lowered = programs[-1].lower(covs_like, *basis_like, ref_like)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        record.update(_analyze(lowered, compiled, chips, t_lower, t_compile))
        # The query path: batched projection onto the served basis.  Its
        # HLO must carry zero collective bytes — the served basis is
        # replicated and a refresh swaps it host-side (double buffer).
        q_like = jax.ShapeDtypeStruct((1024, pcfg.d), jnp.float32)
        q_compiled = svc.query_fn.lower(q_like, ref_like).compile()
        q_coll = H.collective_bytes(q_compiled.as_text())
        record["query_collective_breakdown"] = {
            k: v for k, v in q_coll.items() if v
        }
        record["query_collective_bytes_per_device"] = float(
            sum(q_coll.values())
        )
        if verbose:
            print(
                f"[dryrun] paper-pca-stream (steps={stream_steps}): OK "
                f"chips={chips} compile={t_compile:.1f}s "
                f"refresh_coll={record['collective_bytes_per_device']:.3e}B "
                f"query_coll={record['query_collective_bytes_per_device']:.0f}B"
            )
        return record
    t0 = time.time()

    def job(samples):
        return distributed_pca(
            samples, mesh, pcfg.r,
            n_iter=pcfg.n_iter, solver=pcfg.solver, iters=pcfg.solver_iters,
            plan=pl, membership=mem,
        )

    lowered = jax.jit(job).lower(samples_like)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    record.update(_analyze(lowered, compiled, chips, t_lower, t_compile))
    # Useful flops: covariance (n d^2) + subspace iters (~2 iters n d r) per shard
    mf = 2.0 * pcfg.n_per_shard * pcfg.d * pcfg.d
    record["model_flops_global"] = mf * n_data
    record["model_flops_per_device"] = mf
    record["useful_flops_ratio"] = mf / max(record["flops_per_device"], 1.0)
    if verbose:
        print(
            f"[dryrun] paper-pca (multi_pod={multi_pod}): OK chips={chips} "
            f"compile={t_compile:.1f}s bottleneck={record['bottleneck']} "
            f"coll={record['collective_bytes_per_device']:.3e}B"
        )
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=sorted(ARCHS))
    ap.add_argument("--shape", default=None, choices=sorted(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--eigen", action="store_true",
                    help="train_step with eigen-compressed DP gradients")
    from repro.plan import (
        BACKEND_CHOICES,
        COMM_BITS_CHOICES,
        ORTH_CHOICES,
        PLAN_CHOICES,
        POLAR_CHOICES,
        TOPOLOGY_CHOICES,
    )

    ap.add_argument("--paper-pca", action="store_true")
    ap.add_argument("--backend", default=None, choices=BACKEND_CHOICES,
                    help="aggregation path for --paper-pca (default xla, "
                         "or planner-chosen under --plan auto)")
    ap.add_argument("--polar", default=None, choices=POLAR_CHOICES,
                    help="r x r polar factor for --paper-pca (default "
                         "svd, or planner-chosen under --plan auto)")
    ap.add_argument("--orth", default=None, choices=ORTH_CHOICES,
                    help="per-round orthonormalization for --paper-pca "
                         "(default qr, or planner-chosen under --plan auto)")
    ap.add_argument("--topology", default="auto", choices=TOPOLOGY_CHOICES,
                    help="communication schedule for --paper-pca "
                         "(repro.comm); the record carries the cost-model "
                         "prediction next to the measured HLO bytes; "
                         "'hier' needs a pod axis (--multi-pod or --pods)")
    ap.add_argument("--pods", type=int, default=None,
                    help="with --paper-pca --topology hier: build a bare "
                         "(pods, n/pods) 2-D aggregation mesh instead of "
                         "the production mesh; the record carries the "
                         "per-level (intra/inter) byte prediction")
    ap.add_argument("--comm-bits", default=None, choices=COMM_BITS_CHOICES,
                    help="wire precision of the --paper-pca collectives "
                         "(repro.comm.quantize); the record carries the "
                         "bits prediction next to the measured HLO bytes; "
                         "'auto' defers to the planner, default 32")
    ap.add_argument("--plan", default="none", choices=PLAN_CHOICES,
                    help="'auto': resolve the four --paper-pca knobs with "
                         "the repro.plan cost model (explicit flags are "
                         "pins); 'none': legacy per-knob resolution")
    ap.add_argument("--explain", action="store_true",
                    help="print the scored plan table for --paper-pca "
                         "before lowering")
    ap.add_argument("--calibrate", default=None, metavar="BENCH_JSON",
                    help="refine the planner's constants from a recorded "
                         "bench_aggregate sweep (consulted when the "
                         "planner runs, i.e. under --plan auto)")
    ap.add_argument("--plan-device", default=None,
                    choices=["cpu", "tpu", "gpu"],
                    help="device model the planner scores against; "
                         "default: the host device, so the planned cell "
                         "keeps well-defined cost analysis (pallas cells "
                         "lower interpret-mode/opaque off-TPU).  Use "
                         "'tpu' to plan for the v5e target the roofline "
                         "prices")
    ap.add_argument("--stream-steps", type=int, default=None, metavar="N",
                    help="with --paper-pca: lower the streaming service's "
                         "programs instead of the one-shot job "
                         "(repro.stream) — the steady-state refresh "
                         "(priced ref_broadcast=False, amortized over an "
                         "N-step cadence) and the query path, whose "
                         "measured collective bytes must be zero")
    ap.add_argument("--drop-shards", default=None, metavar="K[,K..]",
                    help="lower the degraded-mesh --paper-pca program "
                         "with these data-axis shards masked dead "
                         "(repro.comm.Membership); the planner prices "
                         "the survivors and the record carries the "
                         "masked-wire prediction next to measured HLO")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--device-count", type=int, default=512,
                    help="reduced placeholder device count for CI smoke")
    ap.add_argument("--accounting", default="extrapolate",
                    choices=["extrapolate", "unrolled", "scan-only"])
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    help="config override key=value (bool/int/float/str); "
                         "used by the §Perf hillclimb variants")
    ap.add_argument("--mesh-shape", default=None,
                    help="alternate chip factorisation, e.g. 32,8 (§Perf)")
    ap.add_argument("--tag", default="", help="suffix for artifact filenames")
    args = ap.parse_args()

    mesh_shape = (
        tuple(int(x) for x in args.mesh_shape.split(",")) if args.mesh_shape else None
    )

    overrides = {}
    for kv in args.overrides:
        k, v = kv.split("=", 1)
        if v in ("true", "True"):
            v = True
        elif v in ("false", "False"):
            v = False
        else:
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
        overrides[k] = v

    pods = []
    if args.single_pod or not args.multi_pod:
        pods.append(False)
    if args.multi_pod or (not args.single_pod and args.all):
        pods.append(True)

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.paper_pca:
        for mp in pods:
            cells.append(("paper-pca", None, mp))
    else:
        archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
        shapes = sorted(SHAPES) if (args.all or not args.shape) else [args.shape]
        for a in archs:
            for s in shapes:
                for mp in pods:
                    cells.append((a, s, mp))

    failures = 0
    for arch, shape, mp in cells:
        tag = f"{arch}__{shape or 'pca'}__{'multipod' if mp else 'singlepod'}"
        if args.eigen:
            tag += "__eigen"
        if args.tag:
            tag += f"__{args.tag}"
        path = os.path.join(args.out, tag + ".json")
        try:
            if arch == "paper-pca":
                cal = None
                if args.calibrate:
                    from repro.plan import load_calibration

                    cal = load_calibration(args.calibrate)
                rec = dryrun_paper_pca(multi_pod=mp, device_count=args.device_count,
                                       backend=args.backend, polar=args.polar,
                                       orth=args.orth, topology=args.topology,
                                       comm_bits=args.comm_bits,
                                       plan="auto" if args.plan == "auto" else None,
                                       explain=args.explain, calibration=cal,
                                       plan_device=args.plan_device,
                                       drop_shards=args.drop_shards,
                                       pods=args.pods,
                                       stream_steps=args.stream_steps)
            else:
                rec = dryrun_cell(
                    arch, shape, multi_pod=mp, eigen=args.eigen,
                    device_count=args.device_count,
                    accounting=args.accounting,
                    overrides=overrides or None,
                    mesh_shape=mesh_shape,
                )
        except Exception as e:
            failures += 1
            rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()}
            print(f"[dryrun] {tag}: FAIL {type(e).__name__}: {e}")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
    print(f"[dryrun] wrote {len(cells)} records to {args.out}; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
