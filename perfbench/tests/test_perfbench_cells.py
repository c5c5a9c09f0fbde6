"""BENCHMARK.json against its contract, cells found by name from files
alone, and the harness refusing to measure without an accelerator."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert BENCH["paths"] == ["perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_names_units_and_keys():
    seen = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert (ROOT / c["file"]).with_suffix(".py").is_file()
        for k in c["reduced"]:
            assert NAME.match(k)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert (HERE / "limits" / f"{w['name']}.json").is_file()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["better"] in ("lower", "higher") and UNIT.match(m["unit"])
    for m in BENCH["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]) and entry["name"] not in seen
            seen.add(entry["name"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_what_it_must(cell):
    c = harness.find_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


def _tiny_files(root: Path) -> None:
    """A second deployment of the paper's job, added as files only."""
    (root / "perfbench" / "configs").mkdir(parents=True)
    for sub in ("traffic", "limits"):
        (root / "perfbench" / sub).mkdir()
    cfg = json.loads((HERE / "configs" / "paper-jobs.json").read_text())
    cfg.update(name="tiny-jobs", rows=1 << 15, split_rows=1 << 12,
               scan_chunk_rows=1 << 12, scan_resamples=16)
    (root / "perfbench/configs/tiny-jobs.json").write_text(json.dumps(cfg))
    shutil.copy(HERE / "configs" / "paper-jobs.py",
                root / "perfbench/configs/tiny-jobs.py")
    (root / "perfbench/traffic/scan-once.json").write_text(
        json.dumps({"driver": "scan"}))
    (root / "perfbench/limits/tiny-jobs.scan-once.json").write_text(
        json.dumps({"answer_gap": 1e-4, "median_bins": 0.01, "se_z": 6.0}))
    bench = dict(BENCH)
    bench["configs"] = BENCH["configs"] + [{
        "name": "tiny-jobs", "source": "test",
        "file": "perfbench/configs/tiny-jobs.json", "reduced": ["rows"],
        "why": "test"}]
    bench["workloads"] = BENCH["workloads"] + [{
        "name": "tiny-jobs.scan-once", "config": "tiny-jobs",
        "traffic": "scan-once", "chips": 1, "why": "test"}]
    bench["end_to_end"] = [dict(m, workloads=m["workloads"]
                                + ["tiny-jobs.scan-once"])
                           if "scan_rows_per_s" == m["name"] else m
                           for m in BENCH["end_to_end"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_cell_added_from_files_alone_runs(tmp_path):
    import time

    import jax
    sys.path.insert(0, str(ROOT / "src"))
    import run as run_mod
    _tiny_files(tmp_path)
    cell = harness.find_cell("tiny-jobs.scan-once", root=tmp_path)
    assert cell.config["rows"] == 1 << 15
    assert {m["name"] for m in cell.end_to_end} == {"scan_rows_per_s",
                                                    "setup_s"}
    out = run_mod.measure(cell, 2**31 + 7, 0.0, False, jax.devices()[:1],
                          None, time.perf_counter())
    res = out["result"]
    assert res["correct"] is True and res["attempted"] == 1
    assert set(res["metrics"]) == {"scan_rows_per_s", "setup_s"}
    facts = out["facts"]
    assert facts["compiles_in_window"] == 0
    assert 0 <= facts["gc_max_s_in_window"] <= facts["gc_s_in_window"]
    assert facts["pass_span_seconds"][0] >= facts["pass_seconds"][0] > 0


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.find_cell("paper-jobs.no-such-traffic")


def test_gc_pauses_times_each_collection():
    import gc
    before = list(gc.callbacks)
    out = {}
    with harness.gc_pauses(out):
        gc.collect()
        gc.collect()
    assert 0 < out["gc_max_s"] < out["gc_s"]
    assert gc.callbacks == before


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_no_accelerator_no_result(cell, trace):
    """Off the chip the harness exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", cell,
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout and '"metrics"' not in p.stdout
    assert "no accelerator" in p.stderr
