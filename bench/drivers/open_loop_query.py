"""Open loop, queries only: the subspace service's query path with no
refresh in the window.

Set-up is ``open_loop_service``'s: it makes the pools from the seed,
builds one ``repro.stream.SubspaceService`` and ingests
``warmup_chunks`` chunks (the service refreshes on the first and, on its
cadence, on the last, so the served basis is of every row ingested), then
answers one query.  The window sends no chunk: query batches are due at
Poisson arrivals, ``query_rate_per_s`` on average (the same gaps for
every seed, in the seed's order), and go to ``project``; a second thread
waits for each answer.  A query's latency runs from when it was due to
when its projection is ready.

The check is ``open_loop_service``'s, with set-up's last refresh as the
one refresh to check: the rows the service counted, the served basis
against the reference span of every row ingested, and a sample of the
answered queries against the float64 projection onto the served basis.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import List

import jax
import numpy as np

from bench.drivers import open_loop_service as ols
from bench.harness import Check, WindowResult


def setup(ctx) -> ols.State:
    st = ols.setup(ctx)
    st.refreshes.append((st.ingested, st.svc.basis))
    return st


def window(ctx, st: ols.State, seconds: float) -> WindowResult:
    mix, svc = ctx.traffic, st.svc
    rng = np.random.default_rng(ctx.seed)
    due_q = ols.arrivals(seconds, float(mix["query_rate_per_s"]), ctx.seed)
    pick = rng.integers(0, len(st.queries), size=len(due_q))
    n_check = min(int(mix["check_queries"]), len(due_q))
    keep = set(rng.choice(len(due_q), size=n_check, replace=False).tolist())
    keep.add(len(due_q) - 1)

    gen_late = np.zeros(len(due_q))
    latency = np.full(len(due_q), np.nan)
    pending: "queue.Queue" = queue.Queue()
    errors: List[BaseException] = []

    def complete():
        try:
            while (item := pending.get()) is not None:
                i, due, out, served = item
                jax.block_until_ready(out)
                latency[i] = time.perf_counter() - due
                if i in keep:
                    st.answers[i] = (int(pick[i]), out, served)
        except Exception as e:  # raised again by the main thread
            errors.append(e)

    waiter = threading.Thread(target=complete, name="bench-answers")
    t0 = time.perf_counter()
    waiter.start()
    try:
        for i, a in enumerate(due_q):
            due = t0 + a
            ols._sleep_until(due)
            gen_late[i] = time.perf_counter() - due
            b0 = svc.basis
            with ctx.span("bench.query"):
                out = svc.project(st.queries[pick[i]])
            pending.put((i, due, out, (b0, svc.basis)))
    finally:
        pending.put(None)
        waiter.join(ols.JOIN_TIMEOUT_S)
    t_end = time.perf_counter()
    if errors:
        raise errors[0]
    if waiter.is_alive():
        raise RuntimeError("the answer thread did not finish")
    answered = int(np.sum(~np.isnan(latency)))
    ctx.counters.update(
        queries=len(due_q), answered=answered, gen_late_s=gen_late.tolist(),
        query_due_s=due_q.tolist(), query_latency_s=latency.tolist())
    ms = lambda xs, q: float(np.percentile(xs, q)) * 1e3
    ctx.log(f"[{ctx.workload['name']}] {len(due_q)} queries; latency ms "
            f"p50/p95/max {ms(latency, 50):.3f}/{ms(latency, 95):.3f}/"
            f"{ms(latency, 100):.3f}; generator late ms max "
            f"{ms(gen_late, 100):.3f}")
    return WindowResult(e2e={"query_p95_ms": ms(latency, 95)},
                        attempted=len(due_q), failed=len(due_q) - answered,
                        window_s=t_end - t0)


def check(ctx, st: ols.State, res: WindowResult) -> List[Check]:
    return ols.check(ctx, st, res)
