"""LiveSession — a standing windowed bootstrap over an ingest stream.

One session = one statistic (optionally windowed via
``core.reduce_api.TumblingWindow`` / ``SlidingWindow``) kept warm against
a stream of ``LogBatch``es.  Every arrival folds O(Δn) work into
mergeable per-pane states and re-emits a ``LiveReport`` (estimate + CI +
stream health), the live form of the paper's ever-improving early
results.

Correctness under stream pathology (the robustness contract, all
asserted bitwise in tests/test_live.py):

* **Exactly-once folding.**  Batches are folded strictly in sequence
  order through a reorder buffer; a re-delivered sequence number is
  counted and dropped.  Because batch ``seq``'s Poisson weight stream is
  keyed ``offset_seed(base_seed, seq)`` (position, not arrival order),
  any duplicated/reordered delivery that folds the same set of batches
  produces bitwise identical states.
* **Watermark / late data.**  The watermark is the contiguous fold point
  (``next_seq`` / ``watermark_row``).  When the newest delivered seq runs
  ``LagPolicy.max_lag_batches`` ahead of it, the missing batches are
  declared lost: their row extent is charged to their panes as invalid
  (so ``p_eff`` drops and the CI widens — EARL §3.4, never a silent
  hole), and the watermark advances.  A lost batch that shows up later is
  folded into its pane if the pane is still live (``late="fold"``) or
  counted-and-dropped (``late="drop"``).
* **Sample shedding.**  When the observed backlog at fold time exceeds
  ``LagPolicy.shed_backlog``, the batch is Poisson-thinned by a seeded
  row mask (survival ``p_shed``, keyed by sequence number) and folded
  through the same exact-0/1 ``valid_mask`` multiply as every degraded
  path — bitwise equal to handing the mask to the kernels directly — and
  the report's ``correct(p_eff)`` widens the CI by the shed fraction.
* **Bounded memory.**  Windowed state is a ring of at most
  ``window.panes`` per-pane states; eviction is dropping a pane and
  re-merging survivors (never subtraction, never re-reading the log).
  The bound is enforced, not just intended: exceeding it raises.
* **Crash safety.**  ``checkpoint=`` snapshots the pane ring + fold
  cursor through ``CheckpointManager`` every ``checkpoint_every`` folds;
  ``resume=True`` restores (fingerprint-gated) and replays the log from
  ``next_seq``.  Kill-at-any-batch resume is bitwise equal to the
  uninterrupted run — position-keyed streams + in-order folding leave
  nothing dependent on wall-clock or arrival history.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager, cursor
from repro.core import accuracy, fold
from repro.core.bootstrap import seed_from_key
from repro.core.reduce_api import Statistic, Window, split_params
from repro.core.streaming import run_fingerprint
from repro.ft.policy import LagPolicy
from repro.live.log import IngestLog, LogBatch


@dataclasses.dataclass
class LiveCounters:
    """Stream-health totals for one standing session."""
    folded: int = 0              # batches folded exactly once
    duplicates: int = 0          # re-deliveries dropped by seq dedup
    reordered: int = 0           # arrivals ahead of the fold point
    gaps_skipped: int = 0        # batches declared lost by the watermark
    gap_rows: int = 0            # rows charged invalid for those batches
    late_folded: int = 0         # lost batches that arrived and folded
    late_dropped: int = 0        # lost batches dropped per policy
    shed_batches: int = 0        # batches folded through a shed mask
    shed_rows: int = 0           # rows removed by shedding


@dataclasses.dataclass(frozen=True)
class LiveReport:
    """One per-arrival emission: the windowed estimate + CI, the
    ``p_eff`` it was corrected with, and where the stream stands."""
    seq: int                     # the batch this emission folded
    watermark_seq: int           # highest contiguously folded seq
    watermark_row: int           # rows accounted below the watermark
    window_start: int            # first row the report covers (pane-aligned)
    window_end: int              # == watermark_row
    rows: int                    # rows charged to the window (incl. lost/shed)
    valid_rows: int              # rows that actually contributed
    p_eff: float                 # valid_rows / rows — the correct() fraction
    panes_live: int              # ring occupancy (<= memory bound)
    shed: bool                   # this fold went through a shed mask
    estimate: Any                # corrected point estimate
    thetas: Any                  # corrected bootstrap distribution
    report: Any                  # AccuracyReport / Group- / KeyedAccuracyReport
    counters: LiveCounters       # snapshot at emission time


@dataclasses.dataclass
class _Pane:
    """Ring slot: per-resample states (leading B), the unweighted estimate
    state, and the rows charged / validated against this pane."""
    states: Any
    est: Any
    rows: int = 0
    valid: int = 0


class LiveSession:
    """Standing session over an ``IngestLog`` (see module docstring).

    ``stat`` is a ``Statistic`` (cumulative over the whole stream — one
    ever-growing pane) or a ``Window`` wrapping one.  ``feed(batch)`` is
    the delivery entry point (fault-injected tests drive it directly);
    ``poll()`` pulls everything new from the log and feeds it.  Both
    return the ``LiveReport``s emitted by the folds they caused.
    """

    def __init__(self, log: Optional[IngestLog], stat, B: int,
                 key: jax.Array, policy: Optional[LagPolicy] = None,
                 alpha: float = 0.05, checkpoint=None,
                 checkpoint_every: int = 1, resume: bool = False,
                 name: str = "live"):
        if isinstance(stat, Window):
            self.window: Optional[Window] = stat
            stat = stat.stat
        else:
            self.window = None
        if not isinstance(stat, Statistic):
            raise TypeError("stat must be a reduce_api.Statistic or a "
                            "Window around one")
        if not getattr(stat, "mergeable", True):
            raise ValueError(
                f"LiveSession folds per-batch states with merge(), but "
                f"{type(stat).__name__} sets mergeable=False")
        if checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}")
        self.log = log
        self.stat = stat
        self.B = int(B)
        self.alpha = float(alpha)
        self.policy = policy if policy is not None else LagPolicy()
        self.name = name
        self._slide = self.window.slide if self.window else None
        #: hard ring-occupancy bound: the window's panes, or the single
        #: cumulative pane.  Enforced after every fold/eviction.
        self.memory_bound = self.window.panes if self.window else 1

        self._spec, self._params = split_params(stat)
        self._base_seed = seed_from_key(key)
        wsz = self.window.size if self.window else 0
        wsl = self.window.slide if self.window else 0
        self.fingerprint = run_fingerprint(
            self._spec, self._params, self.B, int(self._base_seed), wsz, wsl)

        # standing sessions may share a root path: scoped by fingerprint,
        # steps and GC never clobber a peer (tests/test_checkpoint_ft.py)
        self.checkpoint = CheckpointManager.open(
            checkpoint, scope=self.fingerprint, resume=resume)
        self.checkpoint_every = int(checkpoint_every)

        self._dim: Optional[int] = None
        self._ring: Dict[int, _Pane] = {}
        self._buffer: Dict[int, LogBatch] = {}
        self._lost: set = set()
        self._next_seq = 0
        self._max_seen = -1
        self._end_row = 0
        self.counters = LiveCounters()

        if resume:
            self._restore()
        if log is not None:
            log.register(self.name)
            if self._next_seq > 0:
                log.ack(self.name, self._next_seq - 1)

    # -- geometry -------------------------------------------------------
    def _pane_of(self, row: int) -> int:
        return 0 if self._slide is None else int(row) // self._slide

    def _keep_lo(self) -> int:
        """Lowest pane index the ring retains at the current watermark."""
        if self.window is None:
            return 0
        hi = self._pane_of(max(self._end_row - 1, 0))
        return max(0, hi - self.window.panes + 1)

    @property
    def panes_live(self) -> int:
        return len(self._ring)

    @property
    def watermark_seq(self) -> int:
        return self._next_seq - 1

    @property
    def watermark_row(self) -> int:
        return self._end_row

    # -- pane plumbing --------------------------------------------------
    def _init_pane(self) -> _Pane:
        states, est = fold.fresh(fold.empty_carry(self.stat, self.B,
                                                  self._dim))
        return _Pane(states=states, est=est)

    def _pane(self, p: int) -> _Pane:
        if p not in self._ring:
            self._ring[p] = self._init_pane()
        return self._ring[p]

    def _account_gap(self, row_a: int, row_b: int) -> None:
        """Charge lost rows [row_a, row_b) to their panes as invalid —
        they widen ``p_eff`` instead of leaving a silent hole.  Panes the
        advancing watermark is about to evict anyway are not created."""
        if row_b <= row_a:
            return
        if self.window is None:
            self._pane(0).rows += row_b - row_a
            return
        keep_lo = max(0, self._pane_of(row_b - 1) - self.window.panes + 1)
        for p in range(max(self._pane_of(row_a), keep_lo),
                       self._pane_of(row_b - 1) + 1):
            lo, hi = self.window.pane_rows(p)
            self._pane(p).rows += min(row_b, hi) - max(row_a, lo)

    def _evict(self) -> None:
        keep_lo = self._keep_lo()
        for p in [p for p in self._ring if p < keep_lo]:
            del self._ring[p]
        if len(self._ring) > self.memory_bound:
            raise RuntimeError(
                f"pane ring holds {len(self._ring)} panes, bound is "
                f"{self.memory_bound} — memory-bound invariant violated")

    # -- delivery -------------------------------------------------------
    def feed(self, batch: LogBatch) -> List[LiveReport]:
        """Deliver one batch (any order, any multiplicity); returns the
        reports emitted by the folds this delivery unlocked."""
        s = int(batch.seq)
        self._max_seen = max(self._max_seen, s)
        if s < self._next_seq:
            if s in self._lost:
                return self._late(batch)
            self.counters.duplicates += 1
            return []
        if s in self._buffer:
            self.counters.duplicates += 1
            return []
        if s != self._next_seq:
            self.counters.reordered += 1
        self._buffer[s] = batch
        return self._drain()

    def poll(self) -> List[LiveReport]:
        """Pull everything new from the log and fold it.  The backlog is
        observed FIRST (``_max_seen`` jumps to the newest sealed batch),
        so shedding decisions depend on the log state at poll time — a
        resumed session polling the same log makes the same decisions."""
        if self.log is None:
            raise ValueError("poll() needs a log; feed() batches directly")
        avail = self.log.next_seq
        self._max_seen = max(self._max_seen, avail - 1)
        out: List[LiveReport] = []
        for b in self.log.batches_from(self._next_seq):
            if b.seq in self._buffer or b.seq < self._next_seq:
                continue
            out.extend(self.feed(b))
        return out

    # -- folding --------------------------------------------------------
    def _drain(self) -> List[LiveReport]:
        out: List[LiveReport] = []
        while True:
            if self._next_seq in self._buffer:
                b = self._buffer.pop(self._next_seq)
                # advance BEFORE folding so the emitted report's
                # watermark_seq includes the batch it just folded
                self._next_seq += 1
                out.append(self._fold(b))
                if self.log is not None:
                    self.log.ack(self.name, self._next_seq - 1)
                if (self.checkpoint is not None and
                        self.counters.folded % self.checkpoint_every == 0):
                    self._save()
                continue
            if (self._buffer and
                    self._max_seen - self._next_seq
                    >= self.policy.max_lag_batches):
                # watermark advance: the gap [next_seq, first buffered)
                # is declared lost — charged invalid, CI widens
                m = min(self._buffer)
                self.counters.gaps_skipped += m - self._next_seq
                gap = self._buffer[m].row0 - self._end_row
                self.counters.gap_rows += gap
                self._lost.update(range(self._next_seq, m))
                self._account_gap(self._end_row, self._buffer[m].row0)
                self._end_row = self._buffer[m].row0
                self._next_seq = m
                self._evict()
                continue
            return out

    def _masks_for(self, row0: int, nb: int, valid: np.ndarray):
        """(pane, 0/1 mask over the batch) for every pane the rows
        [row0, row0+nb) overlap — masks are ``valid`` restricted to the
        pane's row range, exact 0.0/1.0 f32."""
        if self.window is None:
            return [(0, valid)]
        out = []
        for p in range(self._pane_of(row0), self._pane_of(row0 + nb - 1) + 1):
            lo, hi = self.window.pane_rows(p)
            m = np.zeros(nb, np.float32)
            a, b = max(lo, row0) - row0, min(hi, row0 + nb) - row0
            m[a:b] = valid[a:b]
            out.append((p, m))
        return out

    def _fold_into_panes(self, batch: LogBatch,
                         valid: np.ndarray) -> None:
        xb = np.asarray(batch.data, np.float32)
        if xb.ndim == 1:
            xb = xb[:, None]
        xd = jax.device_put(xb)
        seq = jnp.asarray(batch.seq, jnp.int32)
        for p, m in self._masks_for(batch.row0, len(xb), valid):
            pane = self._pane(p)
            pane.states, pane.est = fold.fold_chunk_jit(
                pane.states, pane.est, xd, jax.device_put(m),
                self._base_seed, seq, self._params, self._spec, self.B)
            pane.valid += int(m.sum())

    def _shed_mask(self, seq: int, nb: int) -> np.ndarray:
        """Seeded Poisson thinning mask for batch ``seq`` — deterministic
        under (shed_seed, seq), so a resumed run sheds identically."""
        rng = np.random.default_rng((int(self.policy.shed_seed), int(seq)))
        return (rng.random(nb) < self.policy.p_shed).astype(np.float32)

    def _fold(self, batch: LogBatch) -> LiveReport:
        if self._dim is None:
            d = np.asarray(batch.data)
            self._dim = 1 if d.ndim == 1 else int(d.shape[1])
        nb = batch.rows
        lag = self._max_seen - batch.seq
        shed = (self.policy.shed_backlog is not None
                and lag > self.policy.shed_backlog)
        valid = self._shed_mask(batch.seq, nb) if shed \
            else np.ones(nb, np.float32)
        if shed:
            self.counters.shed_batches += 1
            self.counters.shed_rows += nb - int(valid.sum())
        # charge the batch's full extent to its panes (shed rows stay in
        # the denominator — that is exactly what widens the CI)
        for p, m in self._masks_for(batch.row0, nb, np.ones(nb, np.float32)):
            self._pane(p).rows += int(m.sum())
        self._fold_into_panes(batch, valid)
        self._end_row = batch.row_end
        self.counters.folded += 1
        self._evict()
        return self._emit(batch.seq, shed)

    def _late(self, batch: LogBatch) -> List[LiveReport]:
        """A batch the watermark already declared lost showed up."""
        self._lost.discard(batch.seq)
        panes = ([0] if self.window is None else
                 list(range(self._pane_of(batch.row0),
                            self._pane_of(batch.row_end - 1) + 1)))
        if (self.policy.late != "fold"
                or any(p not in self._ring for p in panes)):
            self.counters.late_dropped += 1
            return []
        # rows were already charged at gap time; folding now adds their
        # valid contribution under the batch's own position-keyed stream
        self._fold_into_panes(batch,
                              np.ones(batch.rows, np.float32))
        self.counters.late_folded += 1
        return [self._emit(batch.seq, False)]

    # -- reporting ------------------------------------------------------
    def _emit(self, seq: int, shed: bool) -> LiveReport:
        stat = self.stat
        panes = sorted(self._ring)
        merged = self._ring[panes[0]]
        states, est = merged.states, merged.est
        for p in panes[1:]:
            states = jax.vmap(stat.merge)(states, self._ring[p].states)
            est = stat.merge(est, self._ring[p].est)
        rows = sum(self._ring[p].rows for p in panes)
        valid = sum(self._ring[p].valid for p in panes)
        p_eff = (valid / rows) if rows > 0 else 1.0
        thetas = stat.correct(jax.vmap(stat.finalize)(states), p_eff)
        estimate = stat.correct(stat.finalize(est), p_eff)
        window_start = (self._keep_lo() * self._slide
                        if self.window is not None else 0)
        return LiveReport(
            seq=int(seq), watermark_seq=self.watermark_seq,
            watermark_row=self._end_row, window_start=int(window_start),
            window_end=self._end_row, rows=int(rows), valid_rows=int(valid),
            p_eff=float(p_eff), panes_live=len(self._ring), shed=bool(shed),
            estimate=estimate, thetas=thetas,
            report=accuracy.report_for(
                thetas, alpha=self.alpha,
                num_groups=getattr(stat, "num_groups", None)),
            counters=dataclasses.replace(self.counters))

    def report(self) -> Optional[LiveReport]:
        """The current window's report without folding anything."""
        if not self._ring:
            return None
        return self._emit(self.watermark_seq, False)

    # -- crash safety ---------------------------------------------------
    def _save(self) -> None:
        state = {f"p{p}": (self._ring[p].states, self._ring[p].est)
                 for p in sorted(self._ring)}
        self.checkpoint.save(self.counters.folded, state, extra=cursor(
            "live", self.fingerprint, next_seq=int(self._next_seq),
            end_row=int(self._end_row), max_seen=int(self._max_seen),
            dim=int(self._dim), lost=sorted(int(s) for s in self._lost),
            counters=dataclasses.asdict(self.counters),
            panes={str(p): [int(self._ring[p].rows),
                            int(self._ring[p].valid)]
                   for p in sorted(self._ring)}))

    def _restore(self) -> None:
        def template(cur):
            carry = jax.eval_shape(lambda: fold.empty_carry(
                self.stat, self.B, int(cur["dim"])))
            return {f"p{p}": carry for p in cur["panes"]}
        state, cur = self.checkpoint.restore_cursor(
            "live", self.fingerprint, template, "LiveSession",
            "(statistic, B, key, window)")
        self._dim = int(cur["dim"])
        for p_str, (rows, valid) in cur["panes"].items():
            states, est = fold.fresh(state[f"p{p_str}"])
            self._ring[int(p_str)] = _Pane(states=states, est=est,
                                           rows=int(rows), valid=int(valid))
        self._next_seq = int(cur["next_seq"])
        self._end_row = int(cur["end_row"])
        self._max_seen = int(cur["max_seen"])
        self._lost = set(int(s) for s in cur["lost"])
        self.counters = LiveCounters(**cur["counters"])
