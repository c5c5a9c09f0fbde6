"""Reduce the program's own spans (``program_spans.py``) in a traced window
on the chip: a cell's window, or the small trace the tests read.

    python3 perfbench/tools/span_probe.py --workload <cell> --seed <n> \
        --seconds <s> [--out probe.json]
    python3 perfbench/tools/span_probe.py --fixture <out.json>

``--workload`` sets the cell up and traces its window as ``run.py --trace
1`` does, then prints one JSON line: the harness's reduction, per program
span its seconds, count and longest span, the device's idle by innermost
span on the window's thread and inside each span, the device planes'
lines, the program's counters over the window, and the per-layer numbers
these give: ``permute_s``, ``ssabe_s`` and ``host_syncs`` per query;
``pass_edge_s`` per pass and ``dispatch_max_ms``.

``--fixture`` traces one two-chunk ``bootstrap_streaming`` pass and one
small ``EarlSession`` (both warmed first) inside a ``bench.window``, then
writes what ``tracing.extract`` and ``program_spans.extract`` keep of it,
trimmed to the window (device operations' names cut to 32 characters),
with its reduction as the expected values.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import program_spans  # noqa: E402
import tracing  # noqa: E402

TRACE_DIR = harness.ROOT / ".perfbench_trace_probe"


def _reduce_both(raw: dict, prog: dict):
    return tracing.reduce(raw), program_spans.reduce({**raw, **prog})


def per_layer(ps: program_spans.ProgramSpans, queries: int, passes: int,
              host_syncs: int) -> dict:
    """The per-layer numbers the program's spans and counter give."""
    out = {}
    if queries:
        out["permute_s"] = ps.total("earl.sampler.permute") / queries
        out["ssabe_s"] = ps.total("earl.ssabe") / queries
        out["host_syncs"] = host_syncs / queries
    if passes:
        out["pass_edge_s"] = (ps.total("earl.stream.open")
                              + ps.total("earl.stream.finalize")) / passes
        out["dispatch_max_ms"] = 1e3 * ps.spans.get(
            "earl.stream.dispatch", {}).get("max_s", 0.0)
    return out


def workload(args) -> dict:
    import jax
    cell = harness.find_cell(args.workload)
    devices = jax.devices()[:cell.chips]
    rec = harness.Recorder()
    run = harness.Run(cell=cell, seed=args.seed, devices=devices, rec=rec,
                      peaks=harness.peaks_for(devices[0].device_kind))
    driver = harness.load_module(
        HERE / "drivers" / f"{cell.traffic['driver']}.py").Driver(run)
    driver.setup()
    from repro import trace
    before = trace.counts()
    with tracing.record(TRACE_DIR):
        e2e = driver.window(args.seconds)
    after = trace.counts()
    raw = tracing.extract(TRACE_DIR)
    prog = program_spans.extract(TRACE_DIR)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    red, ps = _reduce_both(raw, prog)
    facts = driver.facts()
    counters = {k: v - before.get(k, 0) for k, v in after.items()}
    queries, passes = facts.get("queries", 0), facts.get("passes", 0)
    return {
        "workload": cell.name, "seed": args.seed, "e2e": e2e,
        "device": harness.device_facts(devices),
        "reduced": dataclasses.asdict(red),
        "per_layer": per_layer(ps, queries, passes,
                               counters.get(trace.HOST_SYNC, 0)),
        "counters": counters, "queries": queries, "passes": passes,
        "idle_by_program_span": ps.top_idle(10),
        "self_share": {n: ps.self_share(n)
                       for n in ("earl.session.run", "bench.pass")
                       if n in ps.idle_in},
        "idle_in": ps.idle_in, "spans": ps.spans, "lines": raw["lines"],
        "facts": facts}


def fixture(args) -> dict:
    import jax
    import numpy as np

    from repro.core import (EarlSession, Mean, Quantile, StatisticGroup,
                            Std, bootstrap_streaming)
    from repro.data import PreMapSampler, ShardedStore
    rec = harness.Recorder()
    x = np.random.default_rng(0).normal(10.0, 2.0, (1 << 21, 1))
    store = ShardedStore.from_array(x.astype(np.float32), 1 << 20,
                                    interleave=False)
    group = StatisticGroup((Mean(), Quantile(0.5, nbins=2048, lo=9.0,
                                             hi=11.0), Std()))
    key = jax.random.PRNGKey(0)

    def stream():
        return bootstrap_streaming(store, Mean(), B=256, key=key,
                                   chunk=1 << 20)

    def session():
        return EarlSession(PreMapSampler(store, seed=1), group, sigma=0.05,
                           backend="fused_rng").run(key)

    stream()
    session()
    with tracing.record(TRACE_DIR):
        with rec.span("bench.window"):
            with rec.span("bench.pass"):
                stream()
            with rec.span("bench.session"):
                out = session()
            with rec.span("bench.sleep"):
                time.sleep(0.05)
    raw = tracing.extract(TRACE_DIR)
    prog = program_spans.extract(TRACE_DIR)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    window = [s for s in raw["spans"] if s[0] == tracing.WINDOW_SPAN][0]
    lo, hi = window[1], window[2]
    trimmed = {
        "devices": {d: [[n[:32], a, b] for n, a, b in evs
                        if b > lo and a < hi]
                    for d, evs in raw["devices"].items()},
        "spans": raw["spans"],
        "host": [s for s in prog["host"] if s[2] > lo and s[1] < hi]}
    red, ps = _reduce_both(trimmed, trimmed)
    rec_json = {
        "recorded": (f"one {jax.devices()[0].device_kind} chip: inside "
                     "bench.window, one bootstrap_streaming pass of Mean "
                     "over 2^21 rows of N(10, 2) in two 2^20-row chunks, "
                     "B = 256 (bench.pass); one EarlSession of mean, "
                     "median sketch and std to sigma 5% over the same "
                     "rows, fused_rng (bench.session, "
                     f"{out.iterations} round, B-hat {out.B}); then 50 ms "
                     "of host sleep"),
        "raw": trimmed,
        "expected": {"window_s": red.window_s, "busy_s": red.busy_s,
                     "spans": ps.spans, "idle_by_span": ps.idle_by_span,
                     "idle_in": ps.idle_in}}
    Path(args.fixture).parent.mkdir(parents=True, exist_ok=True)
    Path(args.fixture).write_text(json.dumps(rec_json))
    return {"fixture": args.fixture, "expected": rec_json["expected"],
            "top_idle": ps.top_idle(10), "reduced": dataclasses.asdict(red)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out")
    ap.add_argument("--fixture")
    args = ap.parse_args(argv)
    if (args.workload is None) == (args.fixture is None):
        ap.error("give --workload or --fixture")
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    if jax.devices()[0].platform == "cpu":
        print("span_probe: JAX finds no accelerator", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    out = workload(args) if args.workload else fixture(args)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
