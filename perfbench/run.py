"""Run one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; everything else it
needs is found by name under ``perfbench/`` (see ``harness.py``).  Set-up
makes the data from the seed and warms every program the window runs;
then the window is measured for ``--seconds``, with the profiler on under
``--trace 1``.  Afterwards the program's state is freed and what the window
produced is compared with the plain reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end under ``--trace 0``, per-layer under ``--trace 1``),
``device`` and, traced, ``breakdown``; its last key, ``checks``, holds each
number compared with its limit, which also end standard error.  An earlier
line holds host facts: compiles inside the window, the garbage
collector's pauses there, and per traffic what the generator saw.  Without an accelerator, or with fewer chips than the
cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import argparse
import gc
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import tracing  # noqa: E402

TRACE_DIR = harness.ROOT / ".perfbench_trace"


def measure(cell: harness.Cell, seed: int, seconds: float, trace: bool,
            devices: list, peaks, t_start: float) -> dict:
    """Set up, measure and check one run of ``cell``; returns what
    ``harness.emit`` prints, and the traffic driver.  Takes the devices as
    found, so tests can drive it on the CPU."""
    rec = harness.Recorder()
    run = harness.Run(cell=cell, seed=seed, devices=devices, rec=rec,
                      peaks=peaks)
    driver = harness.load_module(
        HERE / "drivers" / f"{cell.traffic['driver']}.py").Driver(run)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    compiles0, loads0 = rec.compiles, rec.cache_hits
    pauses = {}
    with harness.gc_pauses(pauses):
        if trace:
            with tracing.record(TRACE_DIR):
                e2e = driver.window(seconds)
        else:
            e2e = driver.window(seconds)
    facts = {"compiles_in_window": rec.compiles - compiles0,
             "cache_loads_in_window": rec.cache_hits - loads0,
             "compiles_in_setup": compiles0, "cache_loads_in_setup": loads0,
             "setup_s": setup_s, "gc_s_in_window": pauses["gc_s"],
             "gc_max_s_in_window": pauses["gc_max_s"]}
    device = harness.device_facts(devices)
    driver.free()
    gc.collect()
    result = {}
    if trace:
        t0 = time.perf_counter()
        run.trace = tracing.reduce(tracing.extract(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        facts["trace_reduce_s"] = time.perf_counter() - t0
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        metrics = {}
        for m in cell.per_layer:
            value = harness.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    else:
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    failed, checks = driver.check()
    facts.update(driver.facts())
    attempted = facts.get("queries", facts.get("passes",
                                               facts.get("batches", 0)))
    correct = failed == 0 and all(c.ok for c in checks)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device, **result}
    return {"result": out, "checks": checks, "facts": facts,
            "driver": driver}


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("perfbench: JAX finds no accelerator (platform 'cpu'); the "
              "benchmark measures chips only", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"perfbench: {cell.name} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    peaks = harness.peaks_for(devices[0].device_kind)
    harness.use_compile_cache()
    out = measure(cell, args.seed, args.seconds, bool(args.trace), devices,
                  peaks, t_start)
    harness.emit(out["result"], out["checks"], out["facts"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
