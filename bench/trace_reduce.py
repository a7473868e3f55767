"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

* busy time: the union of the intervals in which an operation ran on a
  device, inside the traced window; idle share is 1 - busy / window;
* idle gaps: the holes in that union, each labelled by the host span that
  covers most of it (of equal covers, the shortest: the most specific);
* layer device time: the summed durations of the operations a layer's
  matcher selects;
* exposed collective time: the part of the collectives' intervals in which
  no other operation ran on that device;
* roofline share: the least time the chip could take over the time taken.
  A share over 1.05 means the work was counted too high or the time too
  low, and is refused as an error, never clipped.

Only JAX is needed to read a trace (``jax.profiler.ProfileData``); the
arithmetic below works on plain ``Event`` records, so tests can build them.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
ROOFLINE_LIMIT = 1.05

Interval = Tuple[float, float]
_SHORT = re.compile(r"^%?([^\s=]+) = ")
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    stats: Tuple[Tuple[str, object], ...] = ()

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns

    @property
    def short(self) -> str:
        """The HLO instruction's name (``gram.1`` of ``%gram.1 = f32[...]
        custom-call(...)``), or the whole name where it has no such form."""
        m = _SHORT.match(self.name)
        return m.group(1) if m else self.name

    def field(self, key: str) -> str:
        if key == "name":
            return self.name
        if key == "short":
            return self.short
        for k, v in self.stats:
            if k == key:
                return str(v)
        return ""


@dataclasses.dataclass
class Trace:
    """Device planes (plane name -> line name -> events) and host spans."""

    devices: Dict[str, Dict[str, List[Event]]]
    host: List[Event]

    def device_names(self) -> List[str]:
        return sorted(self.devices, key=_device_order)

    def ops(self, device: str) -> List[Event]:
        return self.devices[device].get(OP_LINE, [])

    def modules(self, device: str) -> List[Event]:
        return self.devices[device].get(MODULE_LINE, [])

    def window(self) -> Interval:
        """The harness's window span, in trace time."""
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        w = max(spans, key=lambda e: e.dur_ns)
        return w.start_ns, w.end_ns


def _device_order(name: str):
    m = re.search(r"(\d+)$", name)
    return int(m.group(1)) if m else 0


class Matcher:
    """Selects events whose named fields (``name`` or a stat key such as
    ``hlo_module`` or ``long_name``) match ``pattern``."""

    def __init__(self, pattern: str, fields: Sequence[str] = ("name",)):
        self.pattern = re.compile(pattern)
        self.fields = tuple(fields)

    def __call__(self, event: Event) -> bool:
        return any(self.pattern.search(event.field(f)) for f in self.fields)


# ------------------------------------------------------------------ load --


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(ev) -> Tuple[Tuple[str, object], ...]:
    out = []
    for item in ev.stats:
        try:
            k, v = item
        except (TypeError, ValueError):
            continue
        out.append((str(k), v))
    return tuple(out)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into device op/module lines and host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name not in (OP_LINE, MODULE_LINE):
                    continue
                lines[line.name] = [
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                          _stats(e))
                    for e in line.events
                ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append(Event(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    return Trace(devices, host)


# ------------------------------------------------------------ intervals --


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy_ns(ops: Sequence[Event], lo: float, hi: float) -> float:
    """Time inside [lo, hi] in which at least one op ran."""
    return total(clip(union((e.start_ns, e.end_ns) for e in ops), lo, hi))


def idle_gaps(ops: Sequence[Event], lo: float, hi: float) -> List[Interval]:
    busy = clip(union((e.start_ns, e.end_ns) for e in ops), lo, hi)
    return subtract([(lo, hi)], busy)


def label(gap: Interval, host: Sequence[Event]) -> str:
    """The host span open in ``gap``: the shortest of those that cover at
    least half of it, or, where none does, the one that covers most.
    ``(no host span)`` where none overlaps."""
    half = (gap[1] - gap[0]) / 2
    best, key = "(no host span)", None
    for e in host:
        cover = min(e.end_ns, gap[1]) - max(e.start_ns, gap[0])
        if cover <= 0:
            continue
        k = (cover >= half, -e.dur_ns if cover >= half else cover)
        if key is None or k > key:
            best, key = e.name, k
    return best


def labelled_gaps(trace: Trace, device: str, lo: float, hi: float,
                  n: int = 10) -> List[Tuple[str, float]]:
    """The n longest idle gaps on ``device``: (host span, seconds)."""
    gaps = sorted(idle_gaps(trace.ops(device), lo, hi),
                  key=lambda g: g[1] - g[0], reverse=True)[:n]
    return [(label(g, trace.host), (g[1] - g[0]) * 1e-9) for g in gaps]


def matched_ns(events: Sequence[Event], match: Callable[[Event], bool],
               lo: float, hi: float) -> float:
    """Summed durations, clipped to [lo, hi], of the events ``match``
    selects."""
    return sum(total(clip([(e.start_ns, e.end_ns)], lo, hi))
               for e in events if match(e))


def exposed_ns(ops: Sequence[Event], collective: Callable[[Event], bool],
               lo: float, hi: float) -> float:
    """Time inside [lo, hi] in which a collective ran and nothing else."""
    coll = clip(union((e.start_ns, e.end_ns) for e in ops if collective(e)),
                lo, hi)
    other = union((e.start_ns, e.end_ns) for e in ops if not collective(e))
    return total(subtract(coll, other))


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The ops that hold no other op: a ``while`` op's interval spans its
    body's ops, which the trace lists too."""
    ordered = sorted(ops, key=lambda e: (e.start_ns, -e.end_ns))
    return [e for e, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or not (nxt.start_ns < e.end_ns
                                   and nxt.end_ns <= e.end_ns)]


def module_of(modules: Sequence[Event]) -> Callable[[Event], str]:
    """Maps an op to the name (without its id) of the module running at
    the op's start."""
    mods = sorted(modules, key=lambda e: e.start_ns)
    starts = [m.start_ns for m in mods]

    def find(op: Event) -> str:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        if i >= 0 and mods[i].end_ns >= op.start_ns:
            return _MODULE_ID.sub("", mods[i].name)
        return "?"

    return find


def top_ops(trace: Trace, lo: float, hi: float, devices: Sequence[str],
            n: int = 10) -> List[Tuple[str, float]]:
    """The n operations (``module/instruction``; loops counted through
    their bodies) with the most device time, averaged over ``devices``:
    (name, seconds)."""
    acc: Dict[str, float] = {}
    for dev in devices:
        find = module_of(trace.modules(dev))
        for e in leaves(trace.ops(dev)):
            t = total(clip([(e.start_ns, e.end_ns)], lo, hi))
            if t > 0:
                key = f"{find(e)}/{e.short}"
                acc[key] = acc.get(key, 0.0) + t
    ranked = sorted(acc.items(), key=lambda kv: kv[1], reverse=True)[:n]
    return [(k, v * 1e-9 / max(len(devices), 1)) for k, v in ranked]


# -------------------------------------------------------------- roofline --


def least_time_s(flops: float, nbytes: float, peak_flops: float,
                 peak_bytes_per_s: float) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def roofline_share(least_s: float, measured_s: float) -> float:
    """least / measured; a share over 1.05 is an error in the count of work
    or in the time, and raises."""
    if measured_s <= 0:
        raise ValueError(f"measured time must be > 0 (got {measured_s})")
    share = least_s / measured_s
    if share > ROOFLINE_LIMIT:
        raise ValueError(
            f"roofline share {share:.4f} exceeds {ROOFLINE_LIMIT}: the work "
            "is counted too high or the time leaves part of it out")
    return share


def device_op_seconds(trace: Trace, match: Callable[[Event], bool],
                      lo: float, hi: float,
                      devices: Sequence[str]) -> Optional[float]:
    """Matched op time summed over ``devices`` (None where none matched)."""
    ns = sum(matched_ns(trace.ops(d), match, lo, hi) for d in devices)
    return ns * 1e-9 if ns > 0 else None
