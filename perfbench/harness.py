"""The benchmark's own plumbing: find a cell by name, time its layers, print
its result.

Everything a cell is made of is found by name under this directory:

* ``configs/<config>.json``   sizes and settings of one deployment;
  ``configs/<config>.py``     its data generator, plain float64 reference
                              and lower-precision control (numpy/jax only,
                              nothing of the program);
* ``traffic/<traffic>.json``  one traffic mix; its ``driver`` names the
                              general generator in ``drivers/`` that reads it;
* ``limits/<cell>.json``      the limit of each number ``correct`` compares;
* ``metrics/<metric>.py``     one reader per per-layer metric.

So a later change adds a cell, a mix or a metric by adding files.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_module(path: Path) -> ModuleType:
    """Import a file by path (file names may hold dots, as metric names do)."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    config_mod: ModuleType
    end_to_end: List[dict]
    per_layer: List[dict]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve ``name`` through ``<root>/BENCHMARK.json`` and the files under
    ``<root>/perfbench`` that it names."""
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    base = root / "perfbench"
    config = read_json(root / cfg_entry["file"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=read_json(base / "traffic" / f"{w['traffic']}.json"),
        limits=read_json(base / "limits" / f"{name}.json"),
        config_mod=load_module(
            (root / cfg_entry["file"]).with_suffix(".py")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def metric_reader(name: str) -> Callable:
    return load_module(HERE / "metrics" / f"{name}.py").read


def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached, so only a checkout's first run compiles."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Recorder:
    """Host-clock spans around the calls into each layer, written into the
    profiler's trace as well (``TraceAnnotation``), and counts of the
    programs XLA built: JAX's compile-duration event fires for a compile
    and for a load from the persistent cache alike, so cache hits are
    counted apart and ``compiles`` holds the compiles alone."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self.programs = 0
        self.cache_hits = 0
        self.totals: Dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event, _duration, **_):
        if event == self.COMPILE_EVENT:
            self.programs += 1

    def _on_count(self, event, **_):
        if event == self.CACHE_HIT_EVENT:
            self.cache_hits += 1

    @property
    def compiles(self) -> int:
        return self.programs - self.cache_hits

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with self._annotation(name):
            yield
        self.totals[name] = self.totals.get(name, 0.0) + \
            time.perf_counter() - t0


@contextlib.contextmanager
def gc_pauses(out: dict):
    """Add to ``out`` the seconds the host spent in Python's garbage
    collector inside the block, in all (``gc_s``) and in its longest pause
    (``gc_max_s``), so that a stall of the host can be told from one of
    the collector."""
    out.setdefault("gc_s", 0.0)
    out.setdefault("gc_max_s", 0.0)
    started = [0.0]

    def on_gc(phase, _info):
        if phase == "start":
            started[0] = time.perf_counter()
            return
        pause = time.perf_counter() - started[0]
        out["gc_s"] += pause
        out["gc_max_s"] = max(out["gc_max_s"], pause)

    gc.callbacks.append(on_gc)
    try:
        yield out
    finally:
        gc.callbacks.remove(on_gc)


@dataclasses.dataclass
class Check:
    """One number ``correct`` compares, with its limit: it passes when it
    is at most the limit (a NaN never passes)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def exact(name: str, value: float) -> Check:
    """A number that must come out exactly 0."""
    return Check(name, float(value), 0.0)


def device_facts(devices) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes in
    use on the fullest of ``devices``."""
    dev = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def emit(result: dict, checks: List[Check], facts: dict) -> None:
    """Print the host facts line, the compared numbers on stderr, and the
    result as the last line of standard output (``checks`` last in it)."""
    print(json.dumps({"host_facts": facts}), flush=True)
    for c in checks:
        print(f"check {c.name} {float(c.value)!r} limit {float(c.limit)!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {c.name: {"value": float(c.value),
                                 "limit": float(c.limit)}
                        for c in checks}
    print(json.dumps(result), flush=True)


def peaks_for(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    table = read_json(HERE / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in perfbench/peaks.json"
                       f" (have {sorted(table['devices'])})")
    return table["devices"][kind]


def derive(seed: int, *tags: int) -> int:
    """A 31-bit seed from the run's seed and tags, for numpy and JAX keys."""
    import numpy as np
    ss = np.random.SeedSequence([int(seed) & ((1 << 64) - 1), *tags])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


@dataclasses.dataclass
class Run:
    """What one process hands its driver."""
    cell: Cell
    seed: int
    devices: list
    rec: Recorder
    records: dict = dataclasses.field(default_factory=dict)
    trace: Optional[object] = None          # tracing.Reduced, --trace 1
    peaks: Optional[dict] = None


def worst(values) -> float:
    """The largest of ``values``; NaN if any is NaN (it never passes)."""
    values = list(values)
    if any(math.isnan(v) for v in values):
        return float("nan")
    return max(values) if values else float("nan")


def se_z(se: float, se_ref: float, B: int) -> float:
    """How far a bootstrap's standard error ``se`` over ``B`` resamples lies
    from the reference's ``se_ref``: |ln(se / se_ref)| in units of its
    spread over B resamples, about sqrt(1 / (2 (B - 1))).  A standard error
    of 0, resamples all alike, reads inf."""
    if not se > 0:
        return float("inf")
    return abs(math.log(se / se_ref)) * math.sqrt((B - 1) / 2.0)


def judge(items: List[dict], limits: dict):
    """(failed, checks) over ``items``, one dict of compared numbers per
    answer: an answer fails when any of its numbers is over its limit, and
    each number's check is its worst over the answers that carry it (an
    exact job's answer carries no bootstrap to compare; no answers at all
    read NaN, which never passes)."""
    failed = sum(any(not item[k] <= limits[k] for k in item)
                 for item in items)
    checks = []
    for k in limits:
        vals = [item[k] for item in items if k in item]
        checks.append(Check(k, worst(vals) if vals or not items
                            else 0.0, limits[k]))
    return failed, checks
