"""Open loop: a row stream and a query stream into the subspace service.

Set-up makes, on the device from the seed, a pool of ``chunk_pool`` row
chunks (``rows_per_chunk`` rows per shard each) and ``query_pool`` query
batches (``query_batch`` rows each), builds one
``repro.stream.SubspaceService`` and warms it with ``warmup_chunks``
chunks (its first refresh, a steady-state refresh) and one query.

The window, ``--seconds`` long, has two sources on their own threads:

* rows: one chunk is due every 1 / ``chunk_rate_per_s`` seconds and goes
  to ``observe``, which refreshes the served basis every ``cadence``
  chunks on the ingesting thread;
* queries: batches are due at Poisson arrivals, ``query_rate_per_s`` on
  average, and go to ``project``; a third thread waits for each answer.

Every seed gets the same set of inter-arrival gaps (quantiles of the
exponential law), in an order drawn from the seed.  A query's latency runs
from when it was due to when its projection is ready; a refresh's lag from
when the chunk that made it due was due to when the new basis is served.

The check, after the window: the rows the service counted against the
rows sent; a sample of the refreshes, drawn from the seed with the last
one, against the reference span of every row ingested up to them; and a
sample of the answered queries against the float64 projection onto the
basis that was served to them.
"""

from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from bench import data, reference as ref
from bench.harness import Check, WindowResult

JOIN_TIMEOUT_S = 120.0


@dataclasses.dataclass
class State:
    chunks: Tuple[jax.Array, ...]
    queries: Tuple[jax.Array, ...]
    svc: Any
    ingested: int = 0
    refreshes: List[Tuple[int, jax.Array]] = dataclasses.field(
        default_factory=list)
    answers: Dict[int, Tuple[int, jax.Array, tuple]] = dataclasses.field(
        default_factory=dict)


class ControlService:
    """The control: the reference in the service's place, one precision
    step below the configuration's, refreshing on the same cadence."""

    def __init__(self, cfg: dict):
        self.cfg, self.prec = cfg, cfg["control_precision"]
        self.gram, self.count, self.step, self.last = None, 0, 0, 0
        self.basis: Optional[jax.Array] = None

    def observe(self, chunks):
        g = ref.gram(chunks[0], self.prec["covariance"])
        self.gram = g if self.gram is None else self.gram + g
        self.count += chunks.shape[1]
        self.step += 1
        if self.basis is None or self.step - self.last >= self.cfg["cadence"]:
            self.basis = jax.block_until_ready(ref.leading_span(
                self.gram / self.count, self.cfg["r"],
                iters=self.cfg["solver_iters"],
                start_key=self.cfg["solver_start_key"],
                precision=self.prec["solve"]))
            self.last = self.step
        return self

    def project(self, q):
        return ref.project(q, self.basis, self.prec["query"])

    @property
    def stats(self):
        return {"rows_seen": self.count}


def _program(cfg: dict, devices):
    from repro.comm import DATA_AXIS
    from repro.launch.mesh import make_mesh
    from repro.stream import SubspaceService

    mesh = make_mesh((len(devices),), (DATA_AXIS,), devices=devices)
    return SubspaceService(mesh, cfg["d"], cfg["r"], cadence=cfg["cadence"],
                           n_iter=cfg["n_iter"], solver=cfg["solver"],
                           iters=cfg["solver_iters"], backend=cfg["backend"])


def _make_pools(key, *, d, r, spectrum, m, n_chunk, chunks, n_query, queries):
    kf, kc, kq = jax.random.split(key, 3)
    factor = data.sampling_factor(kf, d, r, spectrum)
    cs = tuple(data.sample_gaussian(k, factor, m * n_chunk).reshape(
        m, n_chunk, d) for k in jax.random.split(kc, chunks))
    qs = tuple(data.sample_gaussian(k, factor, n_query)
               for k in jax.random.split(kq, queries))
    return cs, qs


def setup(ctx) -> State:
    cfg, mix = ctx.cfg, ctx.traffic
    if ctx.chips != 1:
        raise ValueError("the service's reference covers one shard (m=1)")
    dev = ctx.devices[0]
    make = jax.jit(functools.partial(
        _make_pools, d=cfg["d"], r=cfg["r"], spectrum=cfg["spectrum"],
        m=ctx.chips, n_chunk=cfg["rows_per_chunk"],
        chunks=int(mix["chunk_pool"]), n_query=cfg["query_batch"],
        queries=int(mix["query_pool"])))
    with jax.default_device(dev):
        chunks, queries = make(data.seed_key(ctx.seed))
    jax.block_until_ready((chunks, queries))
    svc = (_program(cfg, ctx.devices) if ctx.system == "program"
           else ControlService(cfg))
    st = State(chunks=chunks, queries=queries, svc=svc)
    for _ in range(int(mix["warmup_chunks"])):
        svc.observe(chunks[st.ingested % len(chunks)])
        st.ingested += 1
    jax.block_until_ready(svc.project(queries[0]))
    jax.block_until_ready(svc.basis)
    return st


def arrivals(seconds: float, rate: float, seed: int) -> np.ndarray:
    """Poisson arrival offsets in [0, seconds): the same gaps for every
    seed (exponential quantiles scaled to the window), in the seed's
    order."""
    n = max(int(rate * seconds), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    gaps = gaps[np.random.default_rng(seed).permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def window(ctx, st: State, seconds: float) -> WindowResult:
    mix = ctx.traffic
    svc = st.svc
    rng = np.random.default_rng(ctx.seed)
    due_q = arrivals(seconds, float(mix["query_rate_per_s"]), ctx.seed)
    pick = rng.integers(0, len(st.queries), size=len(due_q))
    n_check = min(int(mix["check_queries"]), len(due_q))
    keep = set(rng.choice(len(due_q), size=n_check, replace=False).tolist())
    keep.add(len(due_q) - 1)
    rate = float(mix["chunk_rate_per_s"])
    n_chunks = max(int(rate * seconds), 1)

    gen_late = np.zeros(len(due_q))
    latency = np.full(len(due_q), np.nan)
    pending: "queue.Queue" = queue.Queue()
    errors: List[BaseException] = []

    def generate():
        try:
            for i, a in enumerate(due_q):
                due = t0 + a
                _sleep_until(due)
                gen_late[i] = time.perf_counter() - due
                b0 = svc.basis
                with ctx.span("bench.query"):
                    out = svc.project(st.queries[pick[i]])
                pending.put((i, due, out, (b0, svc.basis)))
        except Exception as e:  # raised again by the main thread
            errors.append(e)
        finally:
            pending.put(None)

    def complete():
        try:
            while (item := pending.get()) is not None:
                i, due, out, served = item
                jax.block_until_ready(out)
                latency[i] = time.perf_counter() - due
                if i in keep:
                    st.answers[i] = (int(pick[i]), out, served)
        except Exception as e:
            errors.append(e)

    ingest_late, lags = [], []
    t0 = time.perf_counter()
    threads = [threading.Thread(target=generate, name="bench-queries"),
               threading.Thread(target=complete, name="bench-answers")]
    for th in threads:
        th.start()
    try:
        for i in range(n_chunks):
            due = t0 + i / rate
            with ctx.span("bench.wait_for_chunk"):
                _sleep_until(due)
            ingest_late.append(time.perf_counter() - due)
            before = svc.basis
            with ctx.span("bench.observe"):
                svc.observe(st.chunks[st.ingested % len(st.chunks)])
            st.ingested += 1
            after = svc.basis
            if after is not before:
                lags.append(time.perf_counter() - due)
                st.refreshes.append((st.ingested, after))
    finally:
        for th in threads:
            th.join(JOIN_TIMEOUT_S)
    t_end = time.perf_counter()
    if errors:
        raise errors[0]
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a load thread did not finish")
    answered = int(np.sum(~np.isnan(latency)))
    if not lags:
        raise RuntimeError("no refresh in the window: lengthen it")
    ctx.counters.update(
        queries=len(due_q), answered=answered, chunks=n_chunks,
        refreshes=len(lags), gen_late_s=gen_late.tolist(),
        ingest_late_s=ingest_late, refresh_lag_s=lags,
        query_due_s=due_q.tolist(), query_latency_s=latency.tolist())
    e2e = {"query_p95_ms": float(np.percentile(latency, 95)) * 1e3,
           "refresh_lag_ms": float(np.mean(lags)) * 1e3}
    ms = lambda xs, q: float(np.percentile(xs, q)) * 1e3
    ctx.log(f"[{ctx.workload['name']}] {n_chunks} chunks, {len(lags)} "
            f"refreshes, {len(due_q)} queries; refresh lag ms p50/p99/max "
            f"{ms(lags, 50):.2f}/{ms(lags, 99):.2f}/{ms(lags, 100):.2f}; "
            f"query latency ms p50/p95/max {ms(latency, 50):.2f}/"
            f"{ms(latency, 95):.2f}/{ms(latency, 100):.2f}; late ms max: "
            f"chunks {ms(ingest_late, 100):.2f}, queries {ms(gen_late, 100):.2f}")
    return WindowResult(e2e=e2e, attempted=len(due_q) + n_chunks,
                        failed=len(due_q) - answered, window_s=t_end - t0)


def check(ctx, st: State, res: WindowResult) -> List[Check]:
    cfg, mix = ctx.cfg, ctx.traffic
    prec, lim = cfg["precision"], cfg["limits"]
    n_rows = cfg["rows_per_chunk"]
    rows_seen = int(st.svc.stats["rows_seen"])
    checks = [Check("rows_seen_gap", abs(rows_seen - n_rows * st.ingested),
                    lim["rows_seen_gap"])]

    rng = np.random.default_rng(ctx.seed + 1)
    n_pick = min(int(mix["check_refreshes"]), len(st.refreshes))
    picked = set(rng.choice(len(st.refreshes), size=n_pick,
                            replace=False).tolist())
    picked.add(len(st.refreshes) - 1)
    served = {st.refreshes[j][0]: st.refreshes[j][1] for j in picked}
    # The reference accumulates every chunk ingested, in order, and solves
    # at each sampled refresh.
    worst, acc = 0.0, None
    for k in range(1, max(served) + 1):
        g = ref.gram(st.chunks[(k - 1) % len(st.chunks)][0],
                     prec["covariance"])
        acc = g if acc is None else acc + g
        if k in served:
            want = ref.leading_span(acc / (k * n_rows), cfg["r"],
                                    iters=cfg["solver_iters"],
                                    start_key=cfg["solver_start_key"],
                                    precision=prec["solve"])
            worst = max(worst, ref.sin_theta(ref.to_host(served[k]),
                                             ref.to_host(want)))
    checks.append(Check("refresh_sin_max", worst, lim["refresh_sin_max"]))

    worst = 0.0
    for qi, out, bases in st.answers.values():
        q = st.queries[qi]
        worst = max(worst, min(
            ref.rel_err(out, ref.projection(q, b, prec["query"]))
            for b in {id(b): b for b in bases}.values()))
    checks.append(Check("project_rel_max", worst, lim["project_rel_max"]))
    return checks
