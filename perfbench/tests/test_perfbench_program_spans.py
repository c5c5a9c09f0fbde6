"""The program's own spans in a traced window (``program_spans.py``), the
per-layer numbers they give (``tools/span_probe.py``) and the
``host_syncs`` reader, on the CPU."""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tools"))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import program_spans  # noqa: E402
import span_probe  # noqa: E402
import tracing  # noqa: E402

MS = 1e6  # ns
MAIN, PREFETCH = "/host:CPU#0", "/host:CPU#1"


def _hand_trace():
    """Window [0, 100] ms on the main thread, holding one stream pass
    [0, 90]: open [0, 20] with the first chunk's wait [5, 15] and dispatch
    [15, 20], the second chunk's wait [20, 30] and dispatch [30, 35], the
    drain [35, 80] and finalize [80, 88].  The prefetch thread stages both
    chunks, [2, 12] and [12, 25], and stages a third past the window
    [95, 110].  The device runs [18, 70]."""
    def span(name, a, b, line=MAIN, **meta):
        return [name, a * MS, b * MS, line, meta]
    return {
        "devices": {"/device:TPU:0": [["fold", 18 * MS, 70 * MS]]},
        "host": [span("bench.window", 0, 100), span("bench.pass", 0, 90),
                 span("earl.stream.open", 0, 20),
                 span("earl.stream.wait", 5, 15, chunk=0),
                 span("earl.stream.dispatch", 15, 20, chunk=0),
                 span("earl.stream.wait", 20, 30, chunk=1),
                 span("earl.stream.dispatch", 30, 35, chunk=1),
                 span("earl.stream.drain", 35, 80),
                 span("earl.stream.finalize", 80, 88),
                 span("earl.stream.stage", 2, 12, PREFETCH, chunk=0),
                 span("earl.stream.stage", 12, 25, PREFETCH, chunk=1),
                 span("earl.stream.stage", 95, 110, PREFETCH, chunk=2)],
    }


def test_spans_totals_counts_and_longest_inside_the_window():
    ps = program_spans.reduce(_hand_trace())
    assert ps.window_line == MAIN
    assert ps.spans["earl.stream.wait"] == {
        "s": pytest.approx(0.020), "count": 2, "max_s": pytest.approx(0.010)}
    assert ps.spans["earl.stream.dispatch"]["max_s"] == pytest.approx(0.005)
    # the third stage is clipped to the window's last 5 ms
    assert ps.spans["earl.stream.stage"] == {
        "s": pytest.approx(0.028), "count": 3, "max_s": pytest.approx(0.013)}
    assert "bench.pass" not in ps.spans
    assert ps.total("earl.stream.open") + ps.total("earl.stream.finalize") \
        == pytest.approx(0.028)


def test_idle_goes_to_the_main_threads_innermost_span():
    ps = program_spans.reduce(_hand_trace())
    idle = ps.idle_by_span
    # idle [0, 18] and [70, 100]; the prefetch thread's stage spans over
    # [2, 25] and [95, 100] take none of it
    assert "earl.stream.stage" not in idle
    assert idle["earl.stream.open"] == pytest.approx(0.005)     # [0, 5]
    assert idle["earl.stream.wait"] == pytest.approx(0.010)     # [5, 15]
    assert idle["earl.stream.dispatch"] == pytest.approx(0.003)  # [15, 18]
    assert idle["earl.stream.drain"] == pytest.approx(0.010)    # [70, 80]
    assert idle["earl.stream.finalize"] == pytest.approx(0.008)
    assert idle["bench.pass"] == pytest.approx(0.002)           # [88, 90]
    assert idle[program_spans.OUTSIDE] == pytest.approx(0.010)  # [90, 100]
    assert sum(idle.values()) == pytest.approx(0.048)
    red = tracing.reduce({"devices": _hand_trace()["devices"],
                          "spans": [s[:3] for s in _hand_trace()["host"]
                                    if s[0].startswith("bench.")]})
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)
    top = ps.top_idle(3)
    assert [t for _, t in top] == pytest.approx([0.010] * 3)
    assert {n for n, _ in top} == {"earl.stream.wait", "earl.stream.drain",
                                   program_spans.OUTSIDE}


def test_idle_inside_a_span_and_its_self_share():
    ps = program_spans.reduce(_hand_trace())
    assert ps.idle_in["bench.pass"] == pytest.approx(0.038)
    assert ps.idle_in["earl.stream.open"] == pytest.approx(0.018)
    # of the 38 ms idle inside the pass, 2 ms lie outside every earl.*
    # span of the main thread
    assert ps.self_share("bench.pass") == pytest.approx(2 / 38)
    assert ps.self_share("earl.stream.open") == pytest.approx(5 / 18)
    assert ps.self_share("no.such.span") == 0.0


def test_reduce_needs_a_window():
    t = _hand_trace()
    t["host"] = t["host"][1:]
    with pytest.raises(ValueError):
        program_spans.reduce(t)


def test_per_layer_numbers_from_the_spans():
    ps = program_spans.reduce(_hand_trace())
    out = span_probe.per_layer(ps, queries=0, passes=1, host_syncs=0)
    assert out == {"pass_edge_s": pytest.approx(0.028),
                   "dispatch_max_ms": pytest.approx(5.0)}
    ps.spans.update({"earl.sampler.permute": {"s": 6.0},
                     "earl.ssabe": {"s": 1.0}})
    out = span_probe.per_layer(ps, queries=2, passes=0, host_syncs=126)
    assert out == {"permute_s": 3.0, "ssabe_s": 0.5, "host_syncs": 63.0}


def test_profiler_trace_keeps_program_spans_with_thread_and_metadata(
        tmp_path):
    """A real trace on the CPU: a two-chunk stream pass and a small session
    inside ``bench.window``; the program's spans come back with the main
    thread's line, the prefetch thread's own line, and their metadata."""
    import jax
    import numpy as np

    from repro.core import EarlSession, Mean, bootstrap_streaming
    from repro.data import PreMapSampler, ShardedStore
    rec = harness.Recorder()
    x = np.random.default_rng(0).normal(10.0, 2.0, (1 << 14, 1))
    store = ShardedStore.from_array(x.astype(np.float32), 1 << 13,
                                    interleave=False)
    key = jax.random.PRNGKey(0)
    with tracing.record(tmp_path):
        with rec.span("bench.window"):
            bootstrap_streaming(store, Mean(), B=8, key=key, chunk=1 << 13)
            EarlSession(PreMapSampler(store, seed=1), Mean(),
                        sigma=0.05).run(key)
    host = program_spans.extract(tmp_path)["host"]
    line = {s[0]: s[3] for s in host}
    main = line["bench.window"]
    for name in ("earl.stream.open", "earl.stream.dispatch",
                 "earl.stream.drain", "earl.stream.finalize",
                 "earl.session.run", "earl.ssabe.B", "earl.session.round"):
        assert line[name] == main, name
    assert line["earl.stream.stage"] != main
    meta = [(s[0], s[4]) for s in host if s[4]]
    assert ("earl.stream.stage", {"chunk": 1}) in meta
    assert ("earl.stream.dispatch", {"chunk": 1}) in meta
    assert ("earl.session.round", {"round": 1}) in meta
    ps = program_spans.reduce({"devices": {}, "host": host})
    assert ps.spans["earl.stream.dispatch"]["count"] == 2
    assert ps.spans["earl.session.run"]["count"] == 1


RECORDED = Path(__file__).resolve().parent / "data" / "trace_program_v5e.json"
STREAM = ("earl.stream.open", "earl.stream.stage", "earl.stream.wait",
          "earl.stream.dispatch", "earl.stream.drain", "earl.stream.finalize")
SESSION = ("earl.sampler.permute", "earl.sampler.take", "earl.session.run",
           "earl.session.pilot", "earl.ssabe", "earl.ssabe.B",
           "earl.ssabe.n", "earl.session.round", "earl.delta.extend",
           "earl.session.result")


def test_recorded_program_trace_reduces():
    """A trace recorded on one v5e chip by ``tools/span_probe.py
    --fixture``: a two-chunk stream pass, a one-round session and 50 ms of
    host sleep inside ``bench.window``."""
    rec = json.loads(RECORDED.read_text())
    raw, exp = rec["raw"], rec["expected"]
    red = tracing.reduce(raw)
    assert red.window_s == pytest.approx(exp["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(exp["busy_s"], rel=1e-9)
    ps = program_spans.reduce(raw)
    assert set(ps.spans) == set(STREAM + SESSION) == set(exp["spans"])
    for name, want in exp["spans"].items():
        got = ps.spans[name]
        assert got["count"] == want["count"], name
        assert got["s"] == pytest.approx(want["s"], rel=1e-9), name
        assert got["max_s"] == pytest.approx(want["max_s"], rel=1e-9), name
    assert ps.idle_by_span == pytest.approx(exp["idle_by_span"], rel=1e-9)
    assert ps.idle_in == pytest.approx(exp["idle_in"], rel=1e-9)
    # every idle stretch of the window is given to one span of the main
    # thread, never to the prefetch thread's stage
    assert sum(ps.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    stage_lines = {s[3] for s in raw["host"] if s[0] == "earl.stream.stage"}
    assert ps.window_line not in stage_lines
    assert "earl.stream.stage" not in ps.idle_by_span
    # stages pair with dispatches by chunk, one per chunk
    chunks = {name: sorted(s[4]["chunk"] for s in raw["host"]
                           if s[0] == name)
              for name in ("earl.stream.stage", "earl.stream.dispatch")}
    assert chunks["earl.stream.stage"] == chunks["earl.stream.dispatch"] \
        == [0, 1]
    assert [s[4] for s in raw["host"] if s[0] == "earl.session.round"] == \
        [{"round": 1}]
    # the session's idle sits in its children, not in its own time
    assert ps.self_share("earl.session.run") <= 0.2
    assert ps.self_share("bench.pass") <= 0.2
    gaps = dict((n, t) for n, t in red.idle_gaps)
    assert gaps["sleep"] >= 0.05


def _run_with_queries(n):
    return SimpleNamespace(records={"queries": [{}] * n})


def test_host_syncs_reads_the_programs_counters(monkeypatch):
    from repro import trace
    reader = harness.metric_reader("host_syncs")
    monkeypatch.setattr(trace, "counts",
                        lambda: {"host_sync": 126, "session_run": 2})
    assert reader(_run_with_queries(1)) == 63.0
    assert reader(_run_with_queries(0)) is None
    monkeypatch.setattr(trace, "counts", lambda: {})
    assert reader(_run_with_queries(1)) is None


def test_host_syncs_is_silent_where_the_program_has_no_counters(
        monkeypatch):
    """A program without ``repro.trace`` gives no reading and no error."""
    import repro
    monkeypatch.delattr(repro, "trace", raising=False)
    monkeypatch.setitem(sys.modules, "repro.trace", None)
    assert harness.metric_reader("host_syncs")(_run_with_queries(3)) is None
