"""From the profiler's trace to device busy time, idle share, top device
operations and idle gaps labelled by what the host was doing.

``record`` wraps the measured window in ``jax.profiler`` with the Python
tracer off; ``extract`` keeps, from the ``.xplane.pb`` it writes, the
operation events of each TPU device plane and the harness's own host spans
(``bench.*`` ``TraceAnnotation``s); ``reduce`` turns those into numbers.
``extract``'s output is plain JSON, so the reduction is tested on a small
recorded trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import re
import shutil
from pathlib import Path
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: lines of a device plane that restate its operations at a coarser grain
_NOT_OPS = {"XLA Modules", "Steps", "Framework Ops", "Framework Name Scope",
            "Source code", "XLA TraceMe", "Host Offload Op"}

Interval = Tuple[float, float]


@contextlib.contextmanager
def record(out_dir: Path):
    """Trace the block into ``out_dir`` (emptied first)."""
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def extract(out_dir: Path) -> dict:
    """Device operation events and ``bench.*`` host spans of the trace under
    ``out_dir``, in nanoseconds on the trace's clock."""
    import jax
    files = glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    prof = jax.profiler.ProfileData.from_file(files[0])
    devices: Dict[str, list] = {}
    spans: list = []
    lines_seen: Dict[str, list] = {}
    for plane in prof.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = list(plane.lines)
            lines_seen[plane.name] = [ln.name for ln in lines]
            names = {ln.name for ln in lines}
            ops = [ln for ln in lines if ln.name == "XLA Ops"] \
                if "XLA Ops" in names else \
                [ln for ln in lines if ln.name not in _NOT_OPS]
            devices[plane.name] = [
                [ev.name, float(ev.start_ns), float(ev.end_ns)]
                for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.end_ns)])
    return {"devices": devices, "spans": spans, "lines": lines_seen}


def union(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Sorted, merged union of ``intervals`` clipped to [lo, hi]."""
    out: List[Interval] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def covered(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted, merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        d = min(a[i][1], b[j][1]) - max(a[i][0], b[j][0])
        if d > 0:
            total += d
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass
class Reduced:
    """The traced window's numbers, all in seconds."""
    window_s: float
    busy_s: float                        # mean over the devices used
    device_ops: List[list]               # [name, seconds], top 10
    idle_gaps: List[list]                # [host span, seconds], top 10
    busy_in: Dict[str, float]            # span name -> busy seconds inside

    @property
    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s


def _segments(spans: List[list], lo: float, hi: float):
    """[lo, hi] cut wherever a host span starts or ends, each piece given
    the innermost (shortest) ``bench.*`` span other than the window that
    holds it."""
    inner = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    edges = sorted({lo, hi} | {t for _, a, b in inner for t in (a, b)
                               if lo < t < hi})
    out, live, k = [], [], 0
    for t0, t1 in zip(edges, edges[1:]):
        while k < len(inner) and inner[k][1] <= t0:
            live.append(inner[k])
            k += 1
        # every span end is an edge, so a span still open at t0 holds
        # the whole piece
        live = [s for s in live if s[2] > t0]
        name = (min(live, key=lambda s: s[2] - s[1])[0][len(SPAN_PREFIX):]
                if live else "outside any span")
        out.append((t0, t1, name))
    return out


def reduce(trace: dict) -> Reduced:
    """Busy is the union of operation intervals on each device inside the
    ``bench.window`` span, averaged over devices; an idle gap is a stretch
    of that window in which no device ran an operation, its time given to
    the innermost host span in which it passed."""
    windows = [s for s in trace["spans"] if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    lo = min(s[1] for s in windows)
    hi = max(s[2] for s in windows)
    spans = trace["spans"]
    dev_busy, op_time = [], {}
    for events in trace["devices"].values():
        dev_busy.append(union([(a, b) for _, a, b in events], lo, hi))
        for name, a, b in events:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                op_time[name] = op_time.get(name, 0.0) + d
    if not dev_busy:
        raise ValueError("the trace holds no TPU device plane")
    merged = union([iv for b in dev_busy for iv in b], lo, hi)
    gaps: Dict[str, float] = {}
    j = 0
    for t0, t1, name in _segments(spans, lo, hi):
        while j < len(merged) and merged[j][1] <= t0:
            j += 1
        k, busy = j, 0.0
        while k < len(merged) and merged[k][0] < t1:
            busy += min(merged[k][1], t1) - max(merged[k][0], t0)
            k += 1
        if t1 - t0 > busy:
            gaps[name] = gaps.get(name, 0.0) + (t1 - t0 - busy)
    busy_in: Dict[str, float] = {}
    for name in {s[0] for s in spans if s[0] != WINDOW_SPAN}:
        iv = union([(a, b) for n, a, b in spans if n == name], lo, hi)
        busy_in[name[len(SPAN_PREFIX):]] = sum(
            overlap(b, iv) for b in dev_busy) / len(dev_busy) * 1e-9
    ns = 1e-9
    return Reduced(
        window_s=(hi - lo) * ns,
        busy_s=sum(map(covered, dev_busy)) / len(dev_busy) * ns,
        device_ops=[[n, t * ns] for n, t in
                    sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
        idle_gaps=[[n, t * ns] for n, t in
                   sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
        busy_in=busy_in)
