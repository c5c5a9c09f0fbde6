"""The program's own spans and counters (``repro.trace``).

* ``EarlSession.run`` and a ``bootstrap_streaming`` pass open every span
  of their layers, with ``round=`` / ``chunk=`` metadata, nested as the
  module docstrings state; the prefetch thread's ``stream.stage`` spans
  carry the chunk that the main thread's ``stream.dispatch`` folds.
* ``host_sync`` counts every read of a device value on the session and
  stream paths: for a session it follows from the B̂ ladder, ``l`` and the
  rounds.
* The sampler draws its permutation lazily: a session that stays inside
  its pilot draws the pilot's rows and materialises nothing; the exact
  job's fallback materialises the whole order once.
* Spans only observe: a session's answer is the same with them recorded.
"""
import contextlib
import sys
import threading

import jax
import numpy as np
import pytest

from repro import trace
from repro.core import EarlSession, Mean, Quantile, Std, StatisticGroup
from repro.core.streaming import bootstrap_streaming
from repro.data import PreMapSampler, ShardedStore
from repro.data.sampler import lazy_cutoff


class SpanLog:
    """Stands in for ``trace.span``: records each span opened, its
    metadata, the span it opened inside on the same thread, and the
    thread."""

    def __init__(self):
        self.spans = []
        self._lock = threading.Lock()
        self._local = threading.local()

    def __call__(self, name, **meta):
        return self._open(trace.PREFIX + name, meta)

    @contextlib.contextmanager
    def _open(self, name, meta):
        stack = self._local.__dict__.setdefault("stack", [])
        with self._lock:
            self.spans.append(dict(
                name=name, meta=meta, parent=stack[-1] if stack else None,
                thread=threading.current_thread().name))
        stack.append(name)
        try:
            yield
        finally:
            stack.pop()

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]

    def parents(self, name):
        return {s["parent"] for s in self.named(name)}


@pytest.fixture
def spans(monkeypatch):
    log = SpanLog()
    monkeypatch.setattr(trace, "span", log)
    return log


DATA = np.random.default_rng(2).normal(50, 5, 400_000).astype(np.float32)


def _store():
    return ShardedStore.from_array(DATA, 50_000, interleave=False)


def _session(stat, sigma, backend=None):
    # the sampler's seed sets the pilot, and with it SSABE's n̂: under this
    # one the cases below run one round, several rounds, or fall back
    return EarlSession(PreMapSampler(_store(), seed=64), stat, sigma=sigma,
                       backend=backend)


# ----------------------------------------------------------------------------
# the session driver and the data layer
# ----------------------------------------------------------------------------
def test_session_spans_nest_as_stated(spans):
    sess = _session(Mean(), sigma=0.0001)       # several rounds
    out = sess.run(jax.random.PRNGKey(0))
    assert not out.fell_back and out.iterations > 1
    assert len(spans.named("earl.sampler.permute")) == 1
    run = spans.named("earl.session.run")
    assert len(run) == 1 and run[0]["parent"] is None
    assert spans.parents("earl.session.pilot") == {"earl.session.run"}
    assert spans.parents("earl.ssabe") == {"earl.session.run"}
    assert spans.parents("earl.ssabe.B") == {"earl.ssabe"}
    assert spans.parents("earl.ssabe.n") == {"earl.ssabe"}
    rounds = spans.named("earl.session.round")
    assert [s["meta"] for s in rounds] == [
        {"round": k} for k in range(1, out.iterations + 1)]
    assert spans.parents("earl.session.round") == {"earl.session.run"}
    assert len(spans.named("earl.delta.extend")) == out.iterations
    assert spans.parents("earl.delta.extend") == {"earl.session.round"}
    assert len(spans.named("earl.session.result")) == out.iterations
    assert spans.parents("earl.session.result") == {"earl.session.round"}
    # one take for the pilot, one for each round's Δ
    assert spans.parents("earl.sampler.take") == {"earl.session.pilot",
                                                  "earl.session.round"}
    assert len(spans.named("earl.sampler.take")) == 1 + out.iterations
    assert not spans.named("earl.session.full_job")


def test_fallback_runs_inside_a_full_job_span(spans):
    sess = _session(StatisticGroup((Mean(), Std())), sigma=0.005,
                    backend="fused_rng")
    out = sess.run(jax.random.PRNGKey(0))
    assert out.fell_back
    assert spans.parents("earl.session.full_job") == {"earl.session.run"}
    assert spans.parents("earl.sampler.take") == {"earl.session.pilot",
                                                  "earl.session.full_job"}
    assert not spans.named("earl.session.round")


def test_lazy_draw_spans_nest_under_take(spans):
    sess = _session(Mean(), sigma=0.0001)
    out = sess.run(jax.random.PRNGKey(0))
    assert not out.fell_back
    # the pilot's take draws the prefix; a round past it draws more
    assert spans.parents("earl.sampler.draw") == {"earl.sampler.take"}
    assert 1 <= len(spans.named("earl.sampler.draw")) <= 1 + out.iterations


def _sampler_counts(before):
    after = trace.counts()
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in ("sampler_rows_drawn", "sampler_full_permute")}


def test_one_round_session_draws_the_pilot_and_permutes_nothing():
    sess = _session(Mean(), sigma=0.005)
    before = trace.counts()
    out = sess.run(jax.random.PRNGKey(0))
    n_pilot = min(out.N, sess.max_pilot,
                  max(sess.min_pilot, int(sess.p_pilot * out.N)))
    assert not out.fell_back and out.iterations == 1
    # the round takes the pilot's prefix again: nothing new is drawn
    assert out.n_used == n_pilot
    assert _sampler_counts(before) == {"sampler_rows_drawn": n_pilot,
                                       "sampler_full_permute": 0}


def test_fallback_materialises_the_order_once():
    sess = _session(StatisticGroup((Mean(), Std())), sigma=0.005,
                    backend="fused_rng")
    before = trace.counts()
    out = sess.run(jax.random.PRNGKey(0))
    assert out.fell_back
    got = _sampler_counts(before)
    assert got["sampler_full_permute"] == 1
    # the exact job's take(0, N) draws the lazy prefix up to K first
    assert got["sampler_rows_drawn"] == lazy_cutoff(out.N)


@pytest.mark.parametrize("stat,sigma,backend,members", [
    (Mean(), 0.005, None, 1),                   # one round
    (Mean(), 0.0001, None, 1),                  # several rounds
    (StatisticGroup((Mean(), Quantile(0.5, lo=30.0, hi=70.0, nbins=64))),
     0.005, "fused_rng", 2),                    # several rounds
], ids=["mean-1-round", "mean-rounds", "group-fused"])
def test_host_syncs_follow_ladder_l_and_rounds(stat, sigma, backend,
                                               members):
    sess = _session(stat, sigma, backend)
    before = trace.counts()
    out = sess.run(jax.random.PRNGKey(0))
    after = trace.counts()
    assert not out.fell_back
    # every member's c_v comes back with the round's one read
    assert len(out.history[-1].get("member_cvs", (out.cv,))) == members
    # one read of every ladder candidate's c_v; one read of each result
    # step's scalars, whatever the members (one per SSABE n-step, one per
    # round); the pilot read on the host, its CLT size, and the run
    # fingerprint's seed
    expected = 1 + sess.l + 2 + out.iterations + 1
    assert after["host_sync"] - before.get("host_sync", 0) == expected
    # compiled steps: the ladder, each n-step and each round
    assert (after["result_steps"] - before.get("result_steps", 0)
            == 1 + sess.l + out.iterations)
    assert after["session_run"] - before.get("session_run", 0) == 1


def test_spans_leave_the_answer_unchanged(spans, monkeypatch):
    traced = _session(Mean(), sigma=0.0001).run(jax.random.PRNGKey(3))
    monkeypatch.undo()
    plain = _session(Mean(), sigma=0.0001).run(jax.random.PRNGKey(3))
    assert spans.spans
    np.testing.assert_array_equal(np.asarray(traced.result),
                                  np.asarray(plain.result))
    assert (traced.cv, traced.n_used, traced.iterations) == \
        (plain.cv, plain.n_used, plain.iterations)


# ----------------------------------------------------------------------------
# the stream loop
# ----------------------------------------------------------------------------
def test_stream_pass_spans_nest_as_stated(spans):
    store = ShardedStore.from_array(DATA[:8192], 4096, interleave=False)
    res = bootstrap_streaming(store, Mean(), B=8, key=jax.random.PRNGKey(1),
                              chunk=4096)
    assert res.stream.n_chunks == 2
    main = threading.current_thread().name
    opening = spans.named("earl.stream.open")
    assert len(opening) == 1 and opening[0]["parent"] is None
    # the first chunk's wait and dispatch fall inside the opening, the
    # second's after it; the last wait finds the end of the stream
    waits = spans.named("earl.stream.wait")
    assert [(s["meta"], s["parent"]) for s in waits] == [
        ({"chunk": 0}, "earl.stream.open"), ({"chunk": 1}, None),
        ({"chunk": 2}, None)]
    dispatches = spans.named("earl.stream.dispatch")
    assert [(s["meta"], s["parent"]) for s in dispatches] == [
        ({"chunk": 0}, "earl.stream.open"), ({"chunk": 1}, None)]
    for name in ("earl.stream.open", "earl.stream.wait",
                 "earl.stream.dispatch", "earl.stream.drain",
                 "earl.stream.finalize"):
        assert {s["thread"] for s in spans.named(name)} == {main}
    assert len(spans.named("earl.stream.drain")) == 1
    assert len(spans.named("earl.stream.finalize")) == 1
    assert spans.parents("earl.stream.drain") == {None}
    assert spans.parents("earl.stream.finalize") == {None}
    stages = spans.named("earl.stream.stage")
    assert [s["meta"] for s in stages] == [{"chunk": 0}, {"chunk": 1}]
    assert {s["thread"] for s in stages} == {"earl-stream-prefetch"}
    assert {s["parent"] for s in stages} == {None}


def test_stream_pass_counts_its_host_syncs():
    store = ShardedStore.from_array(DATA[:8192], 4096, interleave=False)
    stat = StatisticGroup((Mean(), Std()))
    before = trace.counts().get("host_sync", 0)
    bootstrap_streaming(store, stat, B=8, key=jax.random.PRNGKey(1),
                        chunk=4096)
    carry = (len(jax.tree_util.tree_leaves(stat.init_state(1))) * 2)
    # the seed, each leaf of the (states, est) carry read back to make
    # fresh buffers, and three numbers per member in the report
    assert trace.counts()["host_sync"] - before == 1 + carry + 3 * 2


# ----------------------------------------------------------------------------
# the module itself
# ----------------------------------------------------------------------------
def test_span_is_a_named_trace_annotation():
    s = trace.span("stream.dispatch", chunk=3)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass


def test_to_host_counts_device_reads_only():
    before = trace.counts().get("host_sync", 0)
    assert trace.to_host(jax.numpy.float32(2.5), float) == 2.5
    assert trace.to_host(np.float32(2.5), float) == 2.5
    np.testing.assert_array_equal(trace.to_host(jax.numpy.arange(3)),
                                  np.arange(3))
    assert trace.counts()["host_sync"] - before == 2


def test_count_is_thread_safe():
    """More threads than cores, each adding to one counter under a short
    switch interval: a lost update would leave the total short."""
    threads, per = 16, 2000
    before = trace.counts().get("test.stress", 0)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=lambda: [trace.count("test.stress")
                                               for _ in range(per)])
              for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(interval)
    assert trace.counts()["test.stress"] - before == threads * per


def test_counts_is_a_snapshot():
    snap = trace.counts()
    trace.count("test.snapshot", 5)
    now = trace.counts()
    assert now["test.snapshot"] == snap.get("test.snapshot", 0) + 5
    now["test.snapshot"] = -1               # a copy: the counter stays
    assert trace.counts()["test.snapshot"] == snap.get("test.snapshot", 0) + 5
