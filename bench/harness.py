"""One run of one benchmark cell: set-up, a measured window, the check.

Everything that belongs to one configuration, traffic mix, driver or
per-layer metric lives in a file of its own and is found by name:

    BENCHMARK.json                   cells, metrics, configurations
    bench/configs/<config>.json      one deployment (the file BENCHMARK.json names)
    bench/traffic/<traffic>.json     one traffic mix; its "driver" names the driver
    bench/drivers/<driver>.py        setup / window / check for one kind of traffic
    bench/metrics/<metric>.py        read(ctx) for one per-layer metric
    bench/peaks.json                 chip peaks by device kind

A driver module defines ``setup(ctx)``, which makes the data and builds
and warms the system under test (or, where ``ctx.system`` is "control",
the reference one precision step below the configuration's in its
place), ``window(ctx, state, seconds)`` and ``check(ctx, state, result)``.
A metric module defines ``read(ctx)``, which returns a number or None
where it finds nothing to read.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(BENCH_DIR, "out")

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
TRACE_SETTLE_S = 2.0


class SetupError(RuntimeError):
    """The run cannot be made here (no accelerator, too few chips, an
    unknown chip, or the program missing from the checkout)."""


# ----------------------------------------------------------- discovery --


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (metric files carry dots in their names)."""
    if name in sys.modules:
        return sys.modules[name]
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def driver(kind: str, bench_dir: str = BENCH_DIR):
    return load_module(os.path.join(bench_dir, "drivers", f"{kind}.py"),
                       f"bench_driver_{kind}")


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    return load_module(os.path.join(bench_dir, "metrics", f"{name}.py"),
                       "bench_metric_" + name.replace(".", "_"))


def metrics_for(entries: List[dict], cell: dict,
                e2e_of_cell: Optional[List[str]] = None) -> List[dict]:
    """The metric entries a cell reports: those that list it under
    ``workloads``, or, without that key, every cell (an end-to-end metric)
    or every cell that reports the metric it ``moves`` (a per-layer one)."""
    out = []
    for m in entries:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif e2e_of_cell is None or m.get("moves") in e2e_of_cell:
            out.append(m)
    return out


# ------------------------------------------------------------- context --


class CompileCounter:
    """Seconds JAX spends tracing, lowering and compiling, and how many
    executables it builds (compiled, or fetched from the persistent
    cache), from ``jax.monitoring`` events."""

    def __init__(self):
        self.compile_s = 0.0
        self.builds = 0
        self.cache_hits = 0
        self.by_event: Dict[str, float] = {}

    def on_duration(self, event, duration, **_):
        self.by_event[event] = self.by_event.get(event, 0.0) + duration
        if event in COMPILE_EVENTS:
            self.compile_s += duration
        if event == BACKEND_COMPILE:
            self.builds += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def register(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.compile_s, "builds": self.builds,
                "cache_hits": self.cache_hits}


@dataclasses.dataclass
class Context:
    """What a driver and a metric reader see of one run."""

    workload: dict
    cfg: dict
    traffic: dict
    seed: int
    devices: list
    system: str = "program"        # or "control"
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Any = None              # trace_reduce.Trace of the window
    window_ns: Any = None          # (lo, hi) of the window in trace time
    peaks: Optional[dict] = None
    log: Callable[..., None] = print
    compiles: Optional[CompileCounter] = None

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (a no-op when not tracing)."""
        import jax

        return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class WindowResult:
    e2e: Dict[str, float]
    attempted: int
    failed: int
    window_s: float


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


# ---------------------------------------------------------------- run ---


def use_checkout_program(root: str):
    """Import the program from this checkout's ``src`` and nowhere else."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SetupError(f"the program is not in this checkout ({src})")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro

    where = [os.path.realpath(p) for p in repro.__path__]
    if where != [os.path.realpath(os.path.join(src, "repro"))]:
        raise SetupError(f"repro imported from {where}, not from {src}")


def chip_peaks(kind: str, bench_dir: str = BENCH_DIR) -> dict:
    table = load_json(os.path.join(bench_dir, "peaks.json"))["chips"]
    if kind not in table:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return devs


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def trace_dir(name: str) -> str:
    return os.path.join(OUT_DIR, "trace", name)


def reduce_trace(ctx: Context, bench: dict, e2e_names: List[str],
                 log_dir: str):
    """Per-layer metrics, device busy/window and the breakdown of a traced
    window."""
    from bench import trace_reduce as tr

    ctx.trace = tr.load(tr.find_xplane(log_dir))
    lo, hi = ctx.trace.window()
    ctx.window_ns = (lo, hi)
    devs = ctx.trace.device_names()[:ctx.chips]
    ctx.counters["trace_devices"] = devs
    if not devs:
        raise RuntimeError("the trace holds no device plane")
    busy = [tr.busy_ns(ctx.trace.ops(d), lo, hi) for d in devs]
    ctx.counters["busy_s"] = sum(busy) / len(busy) * 1e-9
    ctx.counters["trace_window_s"] = (hi - lo) * 1e-9
    metrics = {}
    for m in metrics_for(bench["per_layer"], ctx.workload, e2e_names):
        value = metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {
        "device_ops": [[n, s] for n, s in
                       tr.top_ops(ctx.trace, lo, hi, devs)],
        "idle_gaps": [[n, s] for n, s in
                      tr.labelled_gaps(ctx.trace, devs[0], lo, hi)],
    }
    return metrics, breakdown


def prepare(name: str, seed: int, *, root: str = ROOT,
            require_tpu: bool = True, system: str = "program",
            overrides: Optional[dict] = None, log=None):
    """Everything a run needs before its set-up: the program imported from
    this checkout, the cell's configuration and traffic (with
    ``overrides``, ``{"config": {...}, "traffic": {...}}``, replacing
    entries: the CPU tests run at small sizes through it), the chips, the
    compile cache and its listener (``ctx.compiles``).  Returns (bench,
    ctx, driver)."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    use_checkout_program(root)
    bench = benchmark(root)
    cell = workload(bench, name)
    cfg = dict(config(bench, cell["config"], root))
    mix = dict(traffic(cell["traffic"]))
    if overrides:
        cfg.update(overrides.get("config", {}))
        mix.update(overrides.get("traffic", {}))

    import jax
    from repro.launch.compile_cache import use_compile_cache

    devices = devices_for(int(cell["chips"]), require_tpu)
    dev = devices[0]
    peaks = chip_peaks(dev.device_kind) if require_tpu else None
    cache = use_compile_cache()
    # Every program goes to the cache, however fast it compiled, so that
    # only a checkout's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    counter.register()
    log(f"[{name}] device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache}; seed {seed}; system {system}")
    ctx = Context(workload=cell, cfg=cfg, traffic=mix, seed=seed,
                  devices=devices[:int(cell["chips"])], system=system,
                  peaks=peaks, log=log, compiles=counter)
    return bench, ctx, driver(mix["driver"])


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, **kw) -> dict:
    """Run one cell once and return the result object that ``main`` prints
    as its last line; ``kw`` as for ``prepare``."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench, ctx, drv = prepare(name, seed, **kw)
    log, cell, dev, counter = ctx.log, ctx.workload, ctx.devices[0], ctx.compiles
    import jax

    state = drv.setup(ctx)
    setup_s = time.perf_counter() - t_start
    at_setup = counter.snapshot()
    log(f"[{name}] setup_s={setup_s:.3f} of which compile_s="
        f"{at_setup['compile_s']:.3f} ({at_setup['builds']} executables, "
        f"{at_setup['cache_hits']} from the persistent cache)")

    log_dir = trace_dir(name)
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        # The tracer's own start-up stalls the host for about a second after
        # start_trace returns; let it pass before the window opens.
        time.sleep(TRACE_SETTLE_S)
    try:
        with ctx.span("bench.window"):
            res = drv.window(ctx, state, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = counter.snapshot()
    ctx.counters.update(
        builds_in_window=in_window["builds"] - at_setup["builds"],
        cache_hits_in_window=in_window["cache_hits"] - at_setup["cache_hits"],
        compile_s_in_window=in_window["compile_s"] - at_setup["compile_s"],
        window_s=res.window_s,
    )
    log(f"[{name}] window_s={res.window_s:.3f}; executables built in the "
        f"window: {ctx.counters['builds_in_window']} "
        f"({ctx.counters['cache_hits_in_window']} from the persistent "
        f"cache, {ctx.counters['compile_s_in_window']:.3f} s)")
    peak = memory_peak_bytes(ctx.devices)

    checks = drv.check(ctx, state, res)
    del state
    correct = bool(checks) and all(c.ok for c in checks)

    e2e_entries = metrics_for(bench["end_to_end"], cell)
    e2e_names = [m["name"] for m in e2e_entries]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out: Dict[str, Any] = {"correct": correct, "attempted": res.attempted,
                           "failed": res.failed}
    if trace:
        metrics, breakdown = reduce_trace(ctx, bench, e2e_names, log_dir)
        device["busy_s"] = ctx.counters["busy_s"]
        device["window_s"] = ctx.counters["trace_window_s"]
        out.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        values = dict(res.e2e, setup_s=setup_s)
        metrics = {}
        for m in e2e_entries:
            if m["name"] not in values:
                raise RuntimeError(f"the driver did not measure {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        out.update(metrics=metrics, device=device)
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}")
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except SetupError as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        print("bench: the run failed; no result", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0
