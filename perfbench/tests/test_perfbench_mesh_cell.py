"""The four-chip ad-hoc cell (``paper-jobs-mesh4.adhoc``) through the harness
at a tiny size, on 4 forced host devices in a subprocess (the flag must be
set before jax imports; tests/conftest.py keeps it out of the test
process), and its readers."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import tracing  # noqa: E402

adhoc_mesh = harness.load_module(HERE / "drivers" / "adhoc_mesh.py")

CELL = "paper-jobs-mesh4.adhoc"

_SCRIPT = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
here, root, tiny = sys.argv[1:4]
sys.path.insert(0, here)
sys.path.insert(0, os.path.join(root, "src"))
import jax
import harness, run
from pathlib import Path
cell = harness.find_cell("paper-jobs-mesh4.adhoc", root=Path(tiny))
out = run.measure(cell, 2**31 + 11, 0.0, False, jax.devices()[:4], None,
                  time.perf_counter())
r = out["driver"].run
print("RESULT " + json.dumps({
    "rows": cell.config["rows"], "chips": cell.chips,
    "result": out["result"], "facts": out["facts"],
    "devices": len(out["driver"].mesh.devices.flat),
    "checks": {c.name: [c.value, c.limit] for c in out["checks"]},
    "mesh_traces": harness.metric_reader("mesh_traces")(r),
    "psum_bytes": harness.metric_reader("psum_bytes")(r)}))
"""


def _tiny_files(root: Path) -> None:
    """The cell's own files with the store cut to 2^20 rows."""
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    for sub in ("configs", "traffic", "limits"):
        (root / "perfbench" / sub).mkdir(parents=True)
    for name in ("paper-jobs.py", "paper-jobs-mesh4.py"):
        shutil.copy(HERE / "configs" / name, root / "perfbench/configs")
    cfg = json.loads((HERE / "configs/paper-jobs-mesh4.json").read_text())
    cfg.update(rows=1 << 20, split_rows=1 << 18)
    (root / "perfbench/configs/paper-jobs-mesh4.json").write_text(
        json.dumps(cfg))
    shutil.copy(HERE / "traffic/adhoc-mesh.json", root / "perfbench/traffic")
    shutil.copy(HERE / f"limits/{CELL}.json", root / "perfbench/limits")


def test_the_mesh_cell_runs_through_measure(tmp_path):
    _tiny_files(tmp_path)
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(HERE), str(ROOT), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    r = json.loads(line[len("RESULT "):])
    assert r["rows"] == 1 << 20 and r["chips"] == 4 and r["devices"] == 4
    res = r["result"]
    assert res["correct"] is True and res["attempted"] >= 1
    assert set(res["metrics"]) == {"query_s", "setup_s"}
    assert r["facts"]["compiles_in_window"] == 0
    assert r["facts"]["fell_back"] == 0
    assert r["mesh_traces"] == 0
    assert r["psum_bytes"] > 0
    # the exchange's checks read the session's psummed states
    value, limit = r["checks"]["weight_z"]
    assert 0 < value <= limit
    assert r["checks"]["mass_gap"] == [0.0, 0.5]


def test_the_mesh_cell_reports_its_metrics():
    c = harness.find_cell(CELL)
    assert c.chips == 4 and c.traffic["driver"] == "adhoc_mesh"
    assert {m["name"] for m in c.end_to_end} == {"query_s", "setup_s"}
    assert {m["name"] for m in c.per_layer} == {
        "mesh_traces", "psum_bytes", "idle_share.mesh4"}


def _run_with(queries=None, trace=None):
    return types.SimpleNamespace(
        records={} if queries is None else {"queries": queries},
        trace=trace)


@pytest.mark.parametrize("name,key", [("mesh_traces", "mesh_traces"),
                                      ("psum_bytes", "mesh_psum_bytes")])
def test_mesh_counter_readers(name, key):
    read = harness.metric_reader(name)
    assert read(_run_with()) is None
    # a program without the counter (no mesh path) reads nothing
    assert read(_run_with([{"counts": {"host_sync": 63}}])) is None
    qs = [{"counts": {key: 3}}, {"counts": {key: 0}}, {"counts": {}}]
    assert read(_run_with(qs)) == 1.0


def test_idle_share_mesh4_is_the_mean_over_four_chips():
    read = harness.metric_reader("idle_share.mesh4")
    assert read(_run_with()) is None
    # chip i busy for 100 (i + 1) ns of a 1000-ns window: mean busy 250
    raw = {"spans": [["bench.window", 0.0, 1000.0]],
           "devices": {f"/device:TPU:{i}": [["op", 0.0, 100.0 * (i + 1)]]
                       for i in range(4)}}
    assert read(_run_with([], tracing.reduce(raw))) == pytest.approx(75.0)


def _group_states(rows, B):
    import jax
    import jax.numpy as jnp

    from repro.core import Mean, Quantile, StatisticGroup, Std
    from repro.core.bootstrap import fused_resample_states
    group = StatisticGroup((Mean(), Quantile(0.5, nbins=64, lo=6.0, hi=14.0),
                            Std()))
    x = jnp.asarray(rows, jnp.float32).reshape(-1, 1)
    return jax.device_get(fused_resample_states(group, jnp.int32(7), x, B))


@pytest.mark.parametrize("fold,ok", [("all shards", True),
                                     ("one shard", False),
                                     ("a shard twice", False)])
def test_weight_z_holds_the_exchange(fold, ok):
    """Resample states over all n rows pass the cell's ``weight_z`` limit
    through ``judge``; the states a fold keeps when it leaves the psum out
    (one shard's quarter of the rows) or adds a shard twice fail it."""
    import numpy as np
    n, B = 8192, 4
    rows = np.random.default_rng(3).normal(10.0, 2.0, n)
    states = _group_states(rows, B)
    if fold == "one shard":
        states = _group_states(rows[: n // 4], B)
    elif fold == "a shard twice":
        states = _group_states(np.concatenate([rows, rows[: n // 4]]), B)
    limits = harness.find_cell(CELL).limits
    z = adhoc_mesh.weight_z(states, n, B)
    failed, checks = harness.judge([{"weight_z": z}],
                                   {"weight_z": limits["weight_z"]})
    assert (failed == 0) is ok and checks[0].ok is ok
    assert z < 4.0 if ok else z > 30.0


@pytest.mark.parametrize("precision,ok", [("float32", True),
                                          ("bfloat16", False)])
def test_mass_gap_holds_the_fold_to_float32(precision, ok):
    """The two accumulators of one fold agree exactly in float32; states
    rounded to bfloat16, as a fold or psum in lower precision leaves them,
    fail the cell's ``mass_gap`` limit through ``judge``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rows = np.random.default_rng(4).normal(10.0, 2.0, 8192)
    states = _group_states(rows, 4)
    if precision == "bfloat16":
        states = jax.tree_util.tree_map(
            lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32),
            states)
    limits = harness.find_cell(CELL).limits
    gap = adhoc_mesh.mass_gap(states)
    failed, checks = harness.judge([{"mass_gap": gap}],
                                   {"mass_gap": limits["mass_gap"]})
    assert (failed == 0) is ok and checks[0].ok is ok
    assert gap == 0.0 if ok else gap >= 1.0
