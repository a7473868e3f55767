"""Read a cell's compared numbers over many seeds, for the program and for
its control, in one process (set-up is paid once per seed, compiles once).

    python3 bench/calibrate.py --workload pca_1chip --seeds 101-112 \
        --control-seeds 201-203 --seconds 3 --out bench/out/cal.json

The program's largest reading of each number over its seeds is the lower
reading a limit is set from; the control's smallest, the upper one.  The
control lowers every step that the configuration's ``control_precision``
names; ``--lower solve`` (or ``covariance``, ``query``, comma-separated)
lowers only those, the rest at the stated precision, to show which number
catches which step.  Not run by the benchmark's own runs.
"""

import argparse
import json
import os
import sys


def seeds(text: str):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--lower", help="steps the control lowers (default: all)")
    ap.add_argument("--out")
    args = ap.parse_args()
    bench = harness.benchmark()
    cfg = harness.config(bench, harness.workload(bench, args.workload)["config"])
    control = dict(cfg["control_precision"])
    if args.lower:
        steps = args.lower.split(",")
        unknown = set(steps) - set(control)
        if unknown:
            ap.error(f"no step {sorted(unknown)} in control_precision")
        control = {k: control[k] if k in steps else cfg["precision"][k]
                   for k in control}
    rows = []
    for system, group in (("program", args.seeds), ("control", args.control_seeds)):
        for seed in group:
            out = harness.run_cell(
                args.workload, seed, args.seconds, False, system=system,
                overrides={"config": {"control_precision": control}})
            row = {"system": system, "seed": seed, "correct": out["correct"],
                   "control_precision": control if system == "control" else None,
                   "checks": {k: v["value"] for k, v in out["checks"].items()},
                   "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
            rows.append(row)
            print(json.dumps(row), flush=True)
    summary = {}
    for system, pick in (("program", max), ("control", min)):
        vals = [r["checks"] for r in rows if r["system"] == system]
        if vals:
            summary[system] = {k: pick(v[k] for v in vals) for k in vals[0]}
    print(json.dumps({"workload": args.workload, "summary": summary}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
