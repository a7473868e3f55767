"""Run distributed PCA and the subspace service once on a TPU, at the
``paper_pca`` width (d=8192, r=128), through the library's entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip collective phase only

One chip runs three phases: one-shot PCA from rows
(``repro.launch.eigen.run``), Algorithm 1/2 over m=8 stacked shards in a
fused-kernel, an unfused-kernel and an XLA cell
(``iterative_refinement``), and the streaming subspace service answering
queries (``repro.launch.serve.serve_subspace``).  ``--chips 4`` runs
``distributed_pca`` from rows, one shard per chip, per topology and wire
precision, each against the serial oracle on one device.

Every phase prints its compile and wall seconds, the distances it checked
and the device's peak bytes.  A failed check or phase exits non-zero and
prints no result; the last line of a passing run is one JSON object,
``{"ok": true, "device": {...}}``.  With no TPU it exits non-zero before
any phase.  One process, no children.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp

D, R, N_PER_SHARD, N_ITER, ITERS = 8192, 128, 65536, 2, 30
STACKED_M = 8
FUSED_RING_R = 64    # the fused ring's VMEM-resident width at d=8192
PARITY = 1e-3        # subspace_dist64 between cells that compute one estimator
CENTRAL_DIST = 0.15  # dist_2 to central_estimate (README quickstart bound)

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Smoke:
    """One run: its devices and seed, the checks that failed, and the
    seconds JAX spent tracing, lowering and compiling."""

    def __init__(self, devices, seed: int):
        self.devices, self.seed = devices, seed
        self.compile_s = 0.0
        self.failures: list = []

    def on_duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    def check(self, phase: str, name: str, value, bound, ok: bool) -> None:
        print(f"[{phase}] check {name}: {value} (bound {bound}) "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        if not ok:
            self.failures.append(f"{phase}: {name}")

    def assert_compiled_kernel(self, phase: str, what: str, compiled) -> None:
        found = "tpu_custom_call" in compiled.as_text()
        self.check(phase, f"{what} holds tpu_custom_call", found, True, found)

    def peak_bytes(self) -> int:
        return max(
            (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for dev in self.devices
        )

    def run_phase(self, name: str, body) -> None:
        """Run one phase; print its compile and wall seconds and the peak
        bytes.  An exception fails the phase (and the run) but not the
        phases after it."""
        print(f"[{name}] start", flush=True)
        c0, t0 = self.compile_s, time.perf_counter()
        try:
            body(self, name)
        except Exception:
            traceback.print_exc()
            self.failures.append(f"{name}: raised")
        wall = time.perf_counter() - t0
        print(f"[{name}] compile_s={self.compile_s - c0:.3f} "
              f"wall_s={wall:.3f} peak_bytes_in_use={self.peak_bytes()}",
              flush=True)


def _problem(seed: int, d: int, r: int):
    """The spiked covariance of ``repro.launch.eigen.run``: (truth, factor,
    row key), all from ``seed``."""
    from repro.data import synthetic as syn

    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    tau = syn.spectrum_m1(d, r, delta=0.2)
    _, u, factor = syn.covariance_from_spectrum(k1, tau)
    return u[:, :r], factor, k2


# --------------------------------------------------------------- one chip --


def phase_oneshot(mesh):
    from repro.launch.eigen import run

    def body(sm, name):
        _, st = run(
            d=D, r=R, n_per_shard=N_PER_SHARD, n_iter=N_ITER,
            solver="subspace", iters=ITERS, seed=sm.seed, mesh=mesh,
            backend="auto",
        )
        print(f"[{name}] plan backend={st['backend']} "
              f"topology={st['topology']} polar={st['polar']} "
              f"orth={st['orth']} m={st['m']} distributed_pca wall_s="
              f"{st['wall_s']:.3f} (first call, compile included)")
        for k in ("dist_aligned", "dist_central", "dist_naive", "dist_local0"):
            print(f"[{name}] {k}={st[k]:.6f}")
        sm.check(name, "dist_aligned <= dist_central + 1e-3",
              st["dist_aligned"], st["dist_central"] + 1e-3,
              st["dist_aligned"] <= st["dist_central"] + 1e-3)
        sm.check(name, "backend auto resolves to the kernels", st["backend"],
              "pallas", st["backend"] == "pallas")

    return body


def phase_stacked():
    from repro.core import (
        central_estimate,
        dist_2,
        empirical_covariance,
        iterative_refinement,
        local_bases,
    )
    from repro.core.metrics import subspace_dist64
    from repro.data import synthetic as syn

    def body(sm, name):
        print(f"[{name}] m={STACKED_M} d={D} r={R} rows per shard "
              f"{N_PER_SHARD} (not reduced: each shard's rows are made, "
              "reduced to its covariance and freed before the next)")
        _, factor, key = _problem(sm.seed, D, R)
        keys = jax.random.split(key, STACKED_M)
        cov_jit = jax.jit(lambda f, k: empirical_covariance(
            syn.sample_gaussian(k, f, N_PER_SHARD), backend="pallas"))
        t0 = time.perf_counter()
        cov_fn = cov_jit.lower(factor, keys[0]).compile()
        print(f"[{name}] covariance compile_s={time.perf_counter() - t0:.3f}")
        sm.assert_compiled_kernel(name, "covariance program", cov_fn)
        t0 = time.perf_counter()
        covs = jnp.stack([cov_fn(factor, k) for k in keys])
        covs.block_until_ready()
        print(f"[{name}] covariance x{STACKED_M} wall_s="
              f"{time.perf_counter() - t0:.3f}")
        t0 = time.perf_counter()
        vs = local_bases(covs, R, method="subspace", iters=ITERS)
        v_cent, _ = central_estimate(covs, R, method="subspace", iters=ITERS)
        jax.block_until_ready((vs, v_cent))
        print(f"[{name}] local bases + central estimate wall_s="
              f"{time.perf_counter() - t0:.3f}")
        del covs
        cells = {
            "fused": dict(backend="pallas", polar="newton-schulz",
                          orth="cholesky-qr2"),
            "unfused": dict(backend="pallas", polar="newton-schulz",
                            orth="qr"),
            "xla": dict(backend="xla"),
        }
        outs = {}
        for cell, kw in cells.items():
            t0 = time.perf_counter()
            fn = iterative_refinement.lower(vs, N_ITER, **kw).compile()
            t_compile = time.perf_counter() - t0
            if kw["backend"] == "pallas":
                sm.assert_compiled_kernel(name, f"{cell} cell", fn)
            fn(vs).block_until_ready()
            t0 = time.perf_counter()
            out = fn(vs)
            out.block_until_ready()
            wall = time.perf_counter() - t0
            outs[cell] = out
            dist = float(dist_2(out, v_cent))
            print(f"[{name}] cell {cell} {kw} compile_s={t_compile:.3f} "
                  f"wall_s={wall:.6f} (warm)")
            sm.check(name, f"{cell} dist_2 to central", f"{dist:.6f}",
                  CENTRAL_DIST, dist < CENTRAL_DIST)
        for cell in ("fused", "unfused"):
            gap = subspace_dist64(outs[cell], outs["xla"])
            sm.check(name, f"{cell} vs xla subspace_dist64", f"{gap:.3e}",
                  PARITY, gap <= PARITY)

    return body


def phase_service(mesh):
    from repro.core import central_estimate, dist_2, empirical_covariance
    from repro.data import synthetic as syn
    from repro.launch.serve import serve_subspace

    steps, rows_per_step, batch = 16, 4096, 256

    def body(sm, name):
        svc, st = serve_subspace(
            d=D, r=R, steps=steps, rows_per_step=rows_per_step, cadence=4,
            queries=4096, batch=batch, mesh=mesh, seed=sm.seed,
        )
        for k in ("step", "rows_seen", "refreshes", "staleness",
                  "ingest_s", "query_s", "queries_per_s"):
            print(f"[{name}] {k}={st[k]}")
        sm.check(name, "refreshes", st["refreshes"], ">= 4",
              st["refreshes"] >= 4)
        # The rows serve_subspace ingested, made again from the same seed.
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(sm.seed), 3)
        tau = syn.spectrum_m1(D, R, delta=0.2)
        _, _, factor = syn.covariance_from_spectrum(k1, tau)
        rows = syn.sample_gaussian(k2, factor, steps * rows_per_step)
        v_cent, _ = central_estimate(
            empirical_covariance(rows)[None], R, method="subspace",
            iters=ITERS)
        del rows
        proj = svc.project(syn.sample_gaussian(k3, factor, batch))
        finite = bool(jnp.all(jnp.isfinite(proj)))
        sm.check(name, "projection shape and finite",
              (tuple(proj.shape), finite), ((batch, R), True),
              tuple(proj.shape) == (batch, R) and finite)
        dist = float(dist_2(svc.basis, v_cent))
        sm.check(name, "served basis dist_2 to central", f"{dist:.6f}",
              CENTRAL_DIST, dist < CENTRAL_DIST)

    return body


# ------------------------------------------------------------- four chips --


def phase_collectives():
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.comm import DATA_AXIS, PARITY_TOL
    from repro.core import (
        distributed_pca,
        empirical_covariance,
        iterative_refinement,
        local_bases,
    )
    from repro.core.metrics import subspace_dist64
    from repro.data import synthetic as syn
    from repro.launch.mesh import make_aggregation_mesh

    def body(sm, name):
        devices = sm.devices
        m = len(devices)
        mesh = make_aggregation_mesh(m)
        _, factor, key = _problem(sm.seed, D, R)
        rows_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
        samples = jax.jit(
            lambda f, k: syn.sample_gaussian(k, f, m * N_PER_SHARD),
            out_shardings=rows_sharding,
        )(factor, key)
        samples.block_until_ready()
        print(f"[{name}] rows {samples.shape} sharding {samples.sharding}")
        for shard in samples.addressable_shards:
            print(f"[{name}]   {shard.device}: rows {shard.index[0]} "
                  f"shape {shard.data.shape}")
        # The serial oracle: the same per-shard covariances, on one device.
        covs = jax.jit(lambda x: jax.vmap(empirical_covariance)(
            x.reshape(m, N_PER_SHARD, D)))(samples)
        covs = jax.device_put(covs, devices[0])
        oracle = {}
        for r in (R, FUSED_RING_R):
            vs = local_bases(covs, r, method="subspace", iters=ITERS)
            oracle[r] = np.asarray(iterative_refinement(vs, N_ITER))
        del covs

        cells = [
            ("psum", R, dict(topology="psum"), None),
            ("gather", R, dict(topology="gather"), None),
            ("ring", R, dict(topology="ring"), None),
            ("ring-int8", R, dict(topology="ring", comm_bits=8), None),
            ("hier", R, dict(topology="hier"), 2),
            ("fused-ring", FUSED_RING_R, dict(
                topology="ring", backend="pallas", polar="newton-schulz",
                orth="cholesky-qr2"), None),
        ]
        for cell, r, kw, pods in cells:
            cell_mesh = make_aggregation_mesh(m, pods=pods) if pods else mesh
            c0, t0 = sm.compile_s, time.perf_counter()
            v = distributed_pca(
                samples, cell_mesh, r, n_iter=N_ITER, solver="subspace",
                iters=ITERS, **kw,
            )
            v.block_until_ready()
            wall = time.perf_counter() - t0
            copies = [np.asarray(s.data) for s in v.addressable_shards]
            same = len(copies) == m and all(
                np.array_equal(c, copies[0]) for c in copies
            )
            bound = PARITY_TOL[8] if kw.get("comm_bits") == 8 else PARITY
            gap = subspace_dist64(copies[0], oracle[r])
            print(f"[{name}] cell {cell} r={r} {kw} pods={pods} "
                  f"compile_s={sm.compile_s - c0:.3f} wall_s={wall:.3f} "
                  "(first call, compile included)")
            sm.check(name, f"{cell} vs serial oracle subspace_dist64",
                  f"{gap:.3e}", bound, gap <= bound)
            sm.check(name, f"{cell} identical on all {m} devices", same, True,
                  same)

    return body


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.comm import DATA_AXIS

    print(f"compile cache: {use_compile_cache()}", flush=True)
    sm = Smoke(devices[:args.chips], args.seed)
    jax.monitoring.register_event_duration_secs_listener(sm.on_duration)
    t0 = time.perf_counter()
    if args.chips == 4:
        sm.run_phase("collectives", phase_collectives())
    else:
        mesh1 = make_mesh((1,), (DATA_AXIS,), devices=sm.devices)
        sm.run_phase("oneshot", phase_oneshot(mesh1))
        sm.run_phase("stacked", phase_stacked())
        sm.run_phase("service", phase_service(mesh1))
    print(f"total wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={sm.compile_s:.3f}", flush=True)
    if sm.failures:
        print("FAILED: " + "; ".join(sm.failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
