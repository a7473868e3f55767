"""The program's own host spans (``repro.*``) in a run's profiler trace.

``repro.core.distributed.distributed_pca`` and
``repro.stream.SubspaceService`` open a ``jax.profiler.TraceAnnotation``
around each host step (``repro.pca``, ``repro.service.refresh.wait_count``
and so on), with the step's ids as keyword arguments (``job``, ``step``,
``refresh``, ``query``).  The trace keeps those ids as stats of the host
event, and each Python thread's events on a line of their own.
``trace_reduce.load`` keeps neither, so the readers of the program's spans
load them here, once per trace file.

A program without those spans (an older checkout, the control systems)
leaves nothing to read: ``window_spans`` then returns None, and so does
every reader built on it.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, Optional, Tuple

from bench import harness
from bench import trace_reduce as tr

PREFIX = "repro."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float
    ids: Tuple[Tuple[str, int], ...] = ()
    thread: int = 0

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns

    def id(self, key: str) -> Optional[int]:
        return dict(self.ids).get(key)


def load(path: str) -> Tuple[Span, ...]:
    """Every ``repro.*`` span of the host plane, in start order; ``thread``
    is the index of the host line (one per thread) that holds it."""
    st = os.stat(path)
    return _load(path, st.st_mtime_ns, st.st_size)


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int, size: int) -> Tuple[Span, ...]:
    from jax.profiler import ProfileData

    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    ids = tuple((k, v) for k, v in tr._stats(e)
                                if isinstance(v, int))
                    out.append(Span(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns, ids, i))
    return tuple(sorted(out, key=lambda s: s.start_ns))


def inside(spans, lo: float, hi: float) -> List[Span]:
    """The spans that lie wholly inside [lo, hi]: one cut by the window's
    edge would count only part of its step."""
    return [s for s in spans if s.start_ns >= lo and s.end_ns <= hi]


def window_spans(ctx) -> Optional[List[Span]]:
    """The program's spans inside the traced window of this run's trace,
    or None where the trace holds no program span."""
    if ctx.trace is None or ctx.window_ns is None:
        return None
    try:
        path = tr.find_xplane(harness.trace_dir(ctx.workload["name"]))
    except FileNotFoundError:
        return None
    spans = load(path)
    if not spans:
        return None
    return inside(spans, *ctx.window_ns)


def named(spans, name: str) -> List[Span]:
    return [s for s in spans if s.name == name]


def by_id(spans, key: str) -> Dict[int, List[Span]]:
    """Spans grouped by one of their ids (spans without it left out)."""
    out: Dict[int, List[Span]] = {}
    for s in spans:
        k = s.id(key)
        if k is not None:
            out.setdefault(k, []).append(s)
    return out
