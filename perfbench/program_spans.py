"""The program's own spans in a traced window, beside the harness's.

``repro.trace.span`` writes each stretch of the program's host work into
the profiler's trace as an ``earl.*`` ``TraceAnnotation``, on the device's
clock.  ``extract`` keeps, from the ``.xplane.pb`` that ``tracing.record``
wrote, every ``earl.*`` and ``bench.*`` host span with the thread (line)
it ran on and its metadata.  ``reduce`` takes that and the device planes
that ``tracing.extract`` keeps, and gives per program span name its host
seconds, count and longest span inside the window, the device's idle
time inside each span of the window's thread, and each idle stretch
given to the innermost span on that thread (``earl.*`` or ``bench.*``),
so that a concurrent span of another thread, such as the stream's
prefetch ``stage``, never takes it.  Everything is plain JSON and is
tested on a small recorded trace.

``tracing.py`` itself is left as it is, so every number it gives reads as
before; this module is read by ``tools/span_probe.py``.
"""
from __future__ import annotations

import dataclasses
import glob
from pathlib import Path
from typing import Dict, List

import tracing

PROGRAM_PREFIX = "earl."
OUTSIDE = "outside any span"


def _is_span(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIX) or \
        name.startswith(tracing.SPAN_PREFIX)


def extract(out_dir: Path) -> dict:
    """``host``: every ``earl.*`` / ``bench.*`` host span as ``[name,
    start_ns, end_ns, line, meta]``, ``line`` naming the host plane's
    thread by its place in the plane and ``meta`` the span's metadata
    (``chunk``, ``round``)."""
    import jax
    files = glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    prof = jax.profiler.ProfileData.from_file(files[0])
    host: list = []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                line = f"{plane.name}#{i}"
                for ev in ln.events:
                    if _is_span(ev.name):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.end_ns), line, dict(ev.stats)])
    return {"host": host}


@dataclasses.dataclass
class ProgramSpans:
    """Seconds, all clipped to the ``bench.window`` span."""
    spans: Dict[str, dict]               # name -> {"s", "count", "max_s"}
    idle_in: Dict[str, float]            # name -> idle seconds inside it
    idle_by_span: Dict[str, float]       # innermost span -> idle seconds
    window_line: str

    def total(self, name: str) -> float:
        return self.spans.get(name, {}).get("s", 0.0)

    def top_idle(self, k: int = 10) -> List[list]:
        return [[n, t] for n, t in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def self_share(self, name: str) -> float:
        """The share of the idle inside ``name`` that no span nested in it
        takes: the idle given to ``name`` itself over the idle inside it."""
        inside = self.idle_in.get(name, 0.0)
        return self.idle_by_span.get(name, 0.0) / inside if inside else 0.0


def _innermost(spans: List[list], lo: float, hi: float):
    """[lo, hi] cut wherever a span starts or ends, each piece given the
    innermost (shortest) of ``spans`` that holds it."""
    spans = sorted(spans, key=lambda s: s[1])
    edges = sorted({lo, hi} | {t for _, a, b in spans for t in (a, b)
                               if lo < t < hi})
    out, live, k = [], [], 0
    for t0, t1 in zip(edges, edges[1:]):
        while k < len(spans) and spans[k][1] <= t0:
            live.append(spans[k])
            k += 1
        live = [s for s in live if s[2] > t0]
        name = (min(live, key=lambda s: s[2] - s[1])[0] if live
                else OUTSIDE)
        out.append((t0, t1, name))
    return out


def reduce(trace: dict) -> ProgramSpans:
    """``trace`` holds ``devices`` (``tracing.extract``) and ``host``
    (``extract``).  Idle is the part of the window in which no device ran
    an operation, as ``tracing.reduce`` counts it."""
    host = trace["host"]
    windows = [s for s in host if s[0] == tracing.WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    lo = min(s[1] for s in windows)
    hi = max(s[2] for s in windows)
    line = windows[0][3]
    busy = tracing.union([(a, b) for events in trace["devices"].values()
                          for _, a, b in events], lo, hi)
    ns = 1e-9
    spans: Dict[str, dict] = {}
    for name, a, b, _, _ in host:
        d = min(b, hi) - max(a, lo)
        if not name.startswith(PROGRAM_PREFIX) or d <= 0:
            continue
        s = spans.setdefault(name, {"s": 0.0, "count": 0, "max_s": 0.0})
        s["s"] += d * ns
        s["count"] += 1
        s["max_s"] = max(s["max_s"], d * ns)
    mine = [s[:3] for s in host
            if s[3] == line and s[0] != tracing.WINDOW_SPAN]
    idle_by: Dict[str, float] = {}
    j = 0
    for t0, t1, name in _innermost(mine, lo, hi):
        while j < len(busy) and busy[j][1] <= t0:
            j += 1
        k, b = j, 0.0
        while k < len(busy) and busy[k][0] < t1:
            b += min(busy[k][1], t1) - max(busy[k][0], t0)
            k += 1
        if t1 - t0 > b:
            idle_by[name] = idle_by.get(name, 0.0) + (t1 - t0 - b) * ns
    idle_in: Dict[str, float] = {}
    for name in {s[0] for s in mine}:
        iv = tracing.union([(a, b) for n, a, b in mine if n == name],
                           lo, hi)
        idle_in[name] = (tracing.covered(iv)
                         - tracing.overlap(busy, iv)) * ns
    return ProgramSpans(spans=spans, idle_in=idle_in, idle_by_span=idle_by,
                        window_line=line)
