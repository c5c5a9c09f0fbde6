"""Record a short trace of the program's stream path on the chip
and write what ``tracing.extract`` keeps of it, and its reduction, as JSON.

    python3 perfbench/tools/trace_probe.py --out trace_probe.json

The file shows how the trace names device planes, lines and kernels, and
a trimmed copy of it is the recorded trace the reduction's tests read.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import tracing  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    import numpy as np
    if jax.devices()[0].platform == "cpu":
        print("trace_probe: no accelerator", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    from repro.core import Mean, bootstrap_streaming
    from repro.data import ShardedStore
    rec = harness.Recorder()
    x = np.random.default_rng(0).standard_normal((1 << 21, 1), np.float32)
    store = ShardedStore.from_array(x, 1 << 20, interleave=False)
    key = jax.random.PRNGKey(0)
    bootstrap_streaming(store, Mean(), B=256, key=key, chunk=1 << 20)
    out_dir = harness.ROOT / ".perfbench_trace_probe"
    with tracing.record(out_dir):
        with rec.span("bench.window"):
            with rec.span("bench.pass"):
                bootstrap_streaming(store, Mean(), B=256, key=key,
                                    chunk=1 << 20)
            with rec.span("bench.sleep"):
                time.sleep(0.05)
    raw = tracing.extract(out_dir)
    red = tracing.reduce(raw)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"raw": raw, "reduced": dataclasses.asdict(red)}, f)
    print(json.dumps({"reduced": dataclasses.asdict(red),
                      "lines": raw["lines"],
                      "spans": raw["spans"],
                      "events": {k: len(v) for k, v in raw["devices"].items()},
                      "first": {k: v[:5] for k, v in raw["devices"].items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
