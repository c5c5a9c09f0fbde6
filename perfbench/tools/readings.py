"""Readings for ``correct``'s limits: the program over many seeds, and the
bfloat16 control over the same rows, in one process on the chip.

    python3 perfbench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --control 3 --seconds 5

For each seed, one run of the cell through the harness at its own size
(a window of ``--seconds``), printing every number compared; for the first
``--control`` seeds also the control's reading over the same answers.  The
lower reading of a number is the largest the program gives; the upper is
the smallest the control gives.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import run as run_mod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("readings: no accelerator", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    peaks = harness.peaks_for(devices[0].device_kind)
    harness.use_compile_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = run_mod.measure(cell, seed, args.seconds, False, devices,
                              peaks, time.perf_counter())
        line = {"workload": cell.name, "seed": seed,
                "correct": out["result"]["correct"],
                "attempted": out["result"]["attempted"],
                "checks": {c.name: float(c.value) for c in out["checks"]},
                "metrics": {k: v["value"] for k, v in
                            out["result"]["metrics"].items()},
                "compiles_in_window": out["facts"]["compiles_in_window"]}
        if i < args.control:
            line["control"] = out["driver"].control()
        print(json.dumps(line), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
