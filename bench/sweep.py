"""Find the highest chunk rate, or query rate, the subspace service
sustains, on the chip.

    python3 bench/sweep.py --workload serve_refresh --rates 64,68,72 \
        --seconds 30 --seed 7 --out bench/out/sweep.json
    python3 bench/sweep.py --workload serve_refresh --vary query \
        --rates 100,800,3200 --seconds 30 --seed 9

One process sets the cell up once, then runs its window once per rate, in
the order given, with the other stream as the traffic file says.  A rate
is sustained when its backlog does not grow: how late each chunk was sent
(``--vary chunk``), or how long each query waited for its answer
(``--vary query``), fitted against time over the second half of the
window, rises by less than ``GROWTH_S`` across the window; with queries
varied, the chunk backlog has to stay flat too.  Each row also gives the
longest single stall of the ingest thread (the largest step in chunk
lateness) and the chunk rate actually served.  Run to choose the rates in
the traffic file; not run by the benchmark's own runs.
"""

import argparse
import json
import os
import sys

import numpy as np

GROWTH_S = 0.1
RATE_KEY = {"chunk": "chunk_rate_per_s", "query": "query_rate_per_s"}


def growth(t, late, seconds: float) -> float:
    """Backlog growth over the window: the slope of lateness against time
    over the second half, times the window's length."""
    t, late = np.asarray(t), np.asarray(late)
    half = len(late) // 2
    if len(late) - half <= 2:
        return 0.0
    return float(np.polyfit(t[half:], late[half:], 1)[0]) * seconds


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--vary", choices=sorted(RATE_KEY), default="chunk")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out")
    args = ap.parse_args()
    _, ctx, drv = harness.prepare(args.workload, args.seed)
    st = drv.setup(ctx)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        ctx.traffic[RATE_KEY[args.vary]] = rate
        st.refreshes.clear()
        st.answers.clear()
        res = drv.window(ctx, st, args.seconds)
        c = ctx.counters
        chunk_rate = float(ctx.traffic["chunk_rate_per_s"])
        late = np.asarray(c["ingest_late_s"])
        chunk_growth = growth(np.arange(len(late)) / chunk_rate, late,
                              args.seconds)
        query_growth = growth(c["query_due_s"], c["query_latency_s"],
                              args.seconds)
        sustained = chunk_growth < GROWTH_S and (
            args.vary == "chunk" or query_growth < GROWTH_S)
        row = {"vary": args.vary, "rate": rate, "seed": args.seed,
               "chunk_rate": chunk_rate,
               "query_rate": float(ctx.traffic["query_rate_per_s"]),
               "backlog_growth_s": chunk_growth,
               "query_backlog_growth_s": query_growth,
               "final_late_s": float(late[-1]), "max_late_s": float(late.max()),
               "max_stall_s": float(np.diff(late).max()) if len(late) > 1 else 0.0,
               "chunks_served_per_s": len(late) / (len(late) / chunk_rate
                                                   + float(late[-1])),
               "gen_late_max_ms": float(np.max(c["gen_late_s"])) * 1e3,
               "sustained": bool(sustained),
               "refreshes": c["refreshes"], **res.e2e}
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["rate"] for r in rows if r["sustained"]]
    knee = max(ok) if ok else None
    print(json.dumps({"vary": args.vary, "knee": knee,
                      "four_fifths": knee and 0.8 * knee}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "knee": knee}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
