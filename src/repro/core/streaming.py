"""Double-buffered streaming bootstrap over a ShardedStore — crash-safe.

``bootstrap_chunked`` assumes the sample is already device-resident; real
EARL runs start from a sharded on-disk store whose rows must cross the
host-to-device boundary first.  Done naively that serializes into
``transfer(chunk i) -> compute(chunk i) -> transfer(chunk i+1) -> ...``
and the wall time is the SUM of transfer and compute.  This driver
overlaps them:

* a host *prefetch thread* pulls fixed-size batches from
  ``ShardedStore.iter_batches(chunk)``, pads the ragged tail, and stages
  each one onto the device with ``jax.device_put`` (an async enqueue on
  the transfer stream) — by the time the main thread wants chunk i+1 its
  H2D copy has been running behind chunk i's compute;
* a bounded hand-off queue (depth 2) gives classic double buffering: one
  chunk in compute, one staged/in flight, and the producer blocks rather
  than staging the whole dataset;
* the main thread folds each staged chunk into the running per-resample
  states with ONE donated jitted update per chunk, so XLA reuses the
  state buffers in place and never blocks on the result until the
  trailing edge (``block_until_ready`` once, after the last dispatch).

The per-chunk update is the SAME math as ``bootstrap_chunked``'s scan
body — chunk i draws its implicit Poisson(1) weights from
``offset_seed(seed_from_key(key), i)`` and the unweighted estimate rides
the same pass — so the streamed result is bitwise identical to
``bootstrap_chunked(store.read_all(), ...)`` under the same
``(key, chunk)`` while peak device residency stays
O(B·d + chunk·d + queue_depth·chunk·d), independent of n.

Crash safety (three orthogonal mechanisms, all off by default):

* ``checkpoint=``/``checkpoint_every=``/``resume=`` — every k chunks the
  donated carry ``(states, est)`` plus a cursor (next chunk index, rows
  consumed, base seed, run fingerprint) is snapshotted atomically through
  ``CheckpointManager``.  Because chunk i's weights are keyed
  ``offset_seed(base_seed, i)`` and the fold is a left-merge in chunk
  order, a killed run resumed from the last checkpoint produces a result
  BITWISE equal to the uninterrupted run — and the resumed pass re-reads
  only the rows past the cursor (``iter_batches(start_row=...)`` skips
  whole splits without opening them).
* ``retry=``/``policy=`` — the prefetch thread reads through
  ``ft.ResilientStore``: per-split checksum + row-count validation,
  bounded retry with exponential backoff, per-read deadline.  Observed
  fault/retry counts surface in ``StreamReport.faults``.
* degradation — with ``policy.on_exhausted="degrade"``, a split that
  fails its whole retry budget is declared LOST mid-run: its rows enter
  the chunk as zeros with a zero ``valid_mask`` (chunk boundaries and
  weight streams stay aligned — the PR 6 masked-weight machinery, no
  recompute of surviving rows), and the final result is corrected by
  ``p·valid_rows/N`` so the CI widens honestly, exactly like the
  dedicated ``valid_mask`` oracle run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import queue as queue_mod
import threading
import time
import zlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.checkpoint.manager import CheckpointManager, cursor
from repro.core import accuracy, fold
from repro.core.bootstrap import BootstrapResult, seed_from_key
from repro.core.reduce_api import Statistic, bind_params, split_params


@dataclasses.dataclass
class StreamReport:
    """Where the streamed run's wall time went (host-side view).

    ``stage_s`` is the prefetch thread's cumulative batch-preparation +
    ``device_put`` enqueue time; ``wait_s`` is how long the main thread
    sat idle on the hand-off queue (transfer-bound when large);
    ``dispatch_s`` is the main thread's time enqueueing the per-chunk
    fold steps, and ``drain_s`` the single trailing-edge
    ``block_until_ready`` after the last of them: the device work still
    queued when the host is done (compute-bound when large).  Perfect
    overlap drives ``wall_s`` toward max(stage, compute) instead of
    their sum.  The same stretches appear in a profiler trace as the
    ``earl.stream.*`` spans (``repro.trace``).

    Fault-tolerance accounting: ``checkpoint_s``/``n_checkpoints`` cost
    of the periodic snapshots, ``resumed_from_chunk`` where a resumed run
    picked up (None for a fresh run), ``faults`` the observed
    fault/retry counters of the resilient read path (None when no
    ``retry``/``policy`` was given), ``lost_splits`` splits degraded to
    masked zeros, ``valid_rows`` rows that actually contributed.
    """
    wall_s: float
    stage_s: float
    wait_s: float
    dispatch_s: float
    drain_s: float
    n_chunks: int
    rows: int
    checkpoint_s: float = 0.0
    n_checkpoints: int = 0
    resumed_from_chunk: Optional[int] = None
    faults: Optional[object] = None          # ft.FaultCounters
    lost_splits: Tuple[int, ...] = ()
    valid_rows: int = -1


@dataclasses.dataclass
class StreamingBootstrapResult(BootstrapResult):
    stream: StreamReport = None


#: the per-chunk fold step.  The pass calls it through this module global,
#: so a test can put another step in its place.
_stream_chunk_jit = fold.fold_chunk_jit


def _put_until(out_q, item, stop) -> bool:
    """Blocking put that aborts when the consumer signals ``stop`` — the
    producer must never deadlock on a full queue after the consumer died
    (the pre-fix failure mode: a poisoned chunk raised in the consumer
    and the prefetch thread blocked forever on ``put``)."""
    while not stop.is_set():
        try:
            out_q.put(item, timeout=0.05)
            return True
        except queue_mod.Full:
            continue
    return False


def _stage_batches(store, chunk: int, out_q, timings: dict, stop,
                   start_row: int = 0,
                   stop_row: Optional[int] = None) -> None:
    """Prefetch-thread body: read → pad → mask → ``device_put`` → enqueue.

    ``device_put`` returns as soon as the H2D copy is enqueued, so the
    transfer of chunk i+1 proceeds while the consumer computes on chunk
    i.  Batches from ``iter_batches`` can be zero-copy views of a split;
    the ``np.ascontiguousarray``/pad copy here also shields the store's
    buffers from the transfer machinery.  Exceptions are forwarded to
    the consumer rather than dying silently on this thread; ``stop``
    (set by the consumer's cleanup) aborts any blocked enqueue.

    Each item carries the chunk's 0/1 validity mask: zeros for the
    padded tail and for rows of splits the resilient read path declared
    lost (``invalid_row_ranges`` — known by yield time, because every
    split feeding a batch is read before the batch is assembled).

    ``stop_row`` bounds the pass to the store's first ``stop_row`` rows
    (the pinned-extent path over a growing durable log): the stream it
    yields — chunk boundaries, ragged tail, masks — is exactly what a
    store holding only those rows would yield, so every bitwise contract
    downstream is preserved.
    """
    stage_s = 0.0
    try:
        row0 = start_row
        for batch in store.iter_batches(chunk, start_row=start_row):
            if stop_row is not None and row0 >= stop_row:
                break
            with trace.span("stream.stage", chunk=row0 // chunk):
                t0 = time.perf_counter()
                xb = np.asarray(batch, np.float32)
                if xb.ndim == 1:
                    xb = xb[:, None]
                if stop_row is not None and row0 + len(xb) > stop_row:
                    xb = xb[:stop_row - row0]
                nb = len(xb)
                mask = np.zeros((chunk,), np.float32)
                mask[:nb] = 1.0
                for lo, hi in (store.invalid_row_ranges()
                               if hasattr(store, "invalid_row_ranges")
                               else ()):
                    a, b = max(lo, row0) - row0, min(hi, row0 + nb) - row0
                    if a < b:
                        mask[a:b] = 0.0
                if nb < chunk:
                    pad = np.zeros((chunk - nb,) + xb.shape[1:], xb.dtype)
                    xb = np.concatenate([xb, pad])
                else:
                    xb = np.ascontiguousarray(xb)
                xd = jax.device_put(xb)
                md = jax.device_put(mask)
                stage_s += time.perf_counter() - t0
            row0 += nb
            if not _put_until(out_q, (xd, md, nb, int(mask.sum())), stop):
                return
        _put_until(out_q, None, stop)
    except BaseException as exc:                # noqa: BLE001 — forwarded
        _put_until(out_q, exc, stop)
    finally:
        timings["stage_s"] = stage_s


def run_fingerprint(spec, params, *extra: int,
                    content: Optional[int] = None) -> str:
    """Digest of everything a bitwise resume contract depends on: the
    statistic's structural spec AND its array parameters (a resumed run
    with different KMeans centroids is a different run) plus the caller's
    integer run knobs (B, chunk, base seed, extents, ...).  Shared by
    ``bootstrap_streaming``, ``EarlSession`` and ``LiveSession``
    checkpoints.

    ``content`` (optional) folds a data-content digest in — see
    ``store_content_digest``: with it, a resume against a store whose
    BYTES changed (same shape) fails the fingerprint check instead of
    silently folding new data under an old carry."""
    h = hashlib.sha256()
    h.update(repr(spec._static_key()).encode())
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    h.update(repr(tuple(int(e) for e in extra)).encode())
    if content is not None:
        h.update(b"content:" + repr(int(content)).encode())
    return h.hexdigest()


def store_content_digest(store, upto_row: Optional[int] = None) -> int:
    """Order-sensitive combination of every split's cached crc32 — the
    opt-in CONTENT half of the resume fingerprint.

    crc32 composes cheaply (one u32 per split, cached on the store after
    first computation), so this costs one pass over the store the first
    time and nothing after — which is exactly why it is opt-in
    (``fingerprint_content=True``) rather than default: hashing a
    bigger-than-memory store on every run would defeat streaming.

    ``upto_row`` restricts the digest to the splits that overlap the
    first ``upto_row`` rows — the pinned-extent path: over an append-only
    log those splits are immutable, so the digest of a prefix stays
    stable while the log grows."""
    h = 0
    for s in range(len(store.splits)):
        if upto_row is not None and int(store.offsets[s]) >= upto_row:
            break
        h = zlib.crc32(int(store.split_checksum(s)).to_bytes(4, "little"),
                       h)
    return h


def bootstrap_streaming(store, stat: Statistic, B: int, key: jax.Array,
                        chunk: int = 65536, p: float = 1.0,
                        alpha: float = 0.05,
                        backend: Optional[str] = "fused_rng",
                        queue_depth: int = 2,
                        checkpoint=None, checkpoint_every: int = 1,
                        resume: bool = False,
                        retry=None, policy=None,
                        fingerprint_content: bool = False,
                        n_rows: Optional[int] = None
                        ) -> StreamingBootstrapResult:
    """Streamed bootstrap over ``store`` (module docstring for the how).

    Matrix-free only: the point of streaming is that nothing of size n
    ever exists on either side of the PCIe link, which needs the
    ``backend="fused_rng"`` in-kernel weight generation.  Returns the
    usual ``BootstrapResult`` fields plus a ``StreamReport``; the result
    is bitwise equal to
    ``bootstrap_chunked(store.read_all(), stat, B, key, chunk=chunk,
    backend="fused_rng")``.

    Crash safety: ``checkpoint=`` (a ``CheckpointManager`` or a root
    path) snapshots the carry every ``checkpoint_every`` chunks;
    ``resume=True`` restores the latest snapshot (fingerprint-checked)
    and continues — bitwise equal to the uninterrupted run.  ``retry=``
    (an ``ft.RetryPolicy``) or ``policy=`` (an ``ft.FailurePolicy``,
    which also decides raise-vs-degrade on budget exhaustion) route the
    prefetch reads through ``ft.ResilientStore``.

    ``fingerprint_content=True`` additionally binds the checkpoint
    fingerprint to the store's BYTES (``store_content_digest`` over the
    cached per-split crc32s): a resume against a same-shape store whose
    contents changed refuses loudly instead of folding new data under an
    old carry.  Off by default — it costs one full read of the store the
    first time (checksums are cached after that), and both the save and
    the resume must opt in for the fingerprints to match.

    ``n_rows=`` pins the pass to the store's first ``n_rows`` rows — the
    stable-extent contract over a GROWING log (``IngestLog`` /
    ``DurableIngestLog``): the result, the fingerprint, the checkpoints
    and the ``p_eff`` denominator are all those of a store holding
    exactly those rows, so a resumed run keeps working (bitwise) even if
    a producer appended more batches in between.  Over an append-only
    log the pinned prefix is immutable, so ``fingerprint_content=True``
    composes with it (the digest covers only that prefix).
    """
    if not isinstance(stat, Statistic):
        raise TypeError("stat must be a reduce_api.Statistic")
    if backend != "fused_rng":
        raise ValueError(
            f"bootstrap_streaming is matrix-free only and got "
            f"backend={backend!r}; the only supported backend is "
            "'fused_rng' (a materialized (B, chunk) weight matrix would "
            "defeat the streaming memory contract)")
    # Fail BEFORE the prefetch thread starts: a non-mergeable statistic
    # cannot fold chunk i+1's delta states into chunk i's carry.
    if not getattr(stat, "mergeable", True):
        raise ValueError(
            f"bootstrap_streaming folds per-chunk states with merge(), but "
            f"{type(stat).__name__} sets mergeable=False — stream a "
            "mergeable statistic, or run the single-pass bootstrap "
            "(backend='fused_rng') on the materialized sample instead")
    if store.N == 0:
        raise ValueError("bootstrap_streaming needs a non-empty store")
    if n_rows is None:
        n_rows = int(store.N)
    elif not 0 < n_rows <= store.N:
        raise ValueError(f"n_rows must be in [1, {store.N}] "
                         f"(the store's current extent), got {n_rows}")
    else:
        n_rows = int(n_rows)
    if queue_depth < 1:
        raise ValueError(f"queue_depth must be >= 1, got {queue_depth}")
    if checkpoint_every < 1:
        raise ValueError(
            f"checkpoint_every must be >= 1, got {checkpoint_every}")
    # earl.stream.open: carry init, fingerprint, thread start and the
    # first wait, up to the return of the first chunk's dispatch
    opening = contextlib.ExitStack()
    opening.enter_context(trace.span("stream.open"))
    with opening:
        head = store.splits[0]
        dim = int(np.prod(head.shape[1:])) if head.ndim > 1 else 1

        mgr = CheckpointManager.open(checkpoint, resume=resume)

        # resilient read path: policy wins over bare retry
        reader = store
        counters = None
        if policy is not None or retry is not None:
            from repro.ft.inject import FaultCounters, ResilientStore
            counters = FaultCounters()
            if policy is not None:
                reader = ResilientStore(store, policy.retry, counters,
                                        on_exhausted=policy.on_exhausted)
            else:
                reader = ResilientStore(store, retry, counters,
                                        on_exhausted="raise")

        spec, params = split_params(stat)
        base_seed = seed_from_key(key)
        seed_int = trace.to_host(base_seed, int)
        fp = run_fingerprint(spec, params, B, chunk, seed_int, n_rows, dim,
                             content=(store_content_digest(store,
                                                           upto_row=n_rows)
                                      if fingerprint_content else None))

        states, est = fold.fresh(fold.empty_carry(stat, B, dim))

        start_chunk = 0
        rows_done = 0
        valid_rows = 0
        prior_lost: Tuple[int, ...] = ()
        resumed_from = None
        if resume:
            (states, est), cur = mgr.restore_cursor(
                "stream", fp, (states, est), "bootstrap_streaming",
                "(statistic, B, chunk, key, store)")
            states, est = fold.fresh((states, est))
            start_chunk = int(cur["next_chunk"])
            rows_done = int(cur["rows_done"])
            valid_rows = int(cur["valid_rows"])
            prior_lost = tuple(cur.get("lost_splits", ()))
            resumed_from = start_chunk

        q = queue_mod.Queue(maxsize=queue_depth)
        timings: dict = {}
        stop = threading.Event()
        producer = threading.Thread(target=_stage_batches,
                                    args=(reader, chunk, q, timings, stop,
                                          rows_done, n_rows),
                                    name="earl-stream-prefetch", daemon=True)
        t_start = time.perf_counter()
        producer.start()

        wait_s = dispatch_s = ckpt_s = 0.0
        n_ckpts = 0
        i = start_chunk

        def _save_checkpoint():
            # the cursor names the NEXT chunk; lost splits ride along so a
            # resumed run keeps correcting by the right surviving fraction.
            lost_now = tuple(sorted(set(prior_lost)
                                    | set(getattr(reader, "lost_splits", ()))))
            mgr.save(i, (states, est), extra=cursor(
                "stream", fp, next_chunk=i, rows_done=rows_done,
                valid_rows=valid_rows, lost_splits=list(lost_now),
                B=int(B), chunk=int(chunk), base_seed=seed_int,
                N=int(n_rows)))

        try:
            while True:
                with trace.span("stream.wait", chunk=i):
                    t0 = time.perf_counter()
                    item = q.get()
                    wait_s += time.perf_counter() - t0
                if item is None:
                    break
                if isinstance(item, BaseException):
                    raise item
                xd, md, nb, nv = item
                with trace.span("stream.dispatch", chunk=i):
                    t0 = time.perf_counter()
                    states, est = _stream_chunk_jit(
                        states, est, xd, md, base_seed,
                        jnp.asarray(i, jnp.int32), params, spec, int(B))
                    dispatch_s += time.perf_counter() - t0
                opening.close()
                rows_done += nb
                valid_rows += nv
                i += 1
                if (mgr is not None
                        and (i - start_chunk) % checkpoint_every == 0):
                    t0 = time.perf_counter()
                    _save_checkpoint()
                    ckpt_s += time.perf_counter() - t0
                    n_ckpts += 1
        finally:
            # a consumer-side failure (poisoned chunk, checkpoint error, kill)
            # must not strand the producer blocked on a full queue: signal
            # stop, drain whatever it already staged, and join it.
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue_mod.Empty:
                pass
            producer.join(timeout=10.0)

    # trailing-edge sync + a final checkpoint if the cadence missed the end
    with trace.span("stream.drain"):
        t0 = time.perf_counter()
        (states, est) = jax.block_until_ready((states, est))
        drain_s = time.perf_counter() - t0
    if mgr is not None and (i - start_chunk) % checkpoint_every != 0:
        t0 = time.perf_counter()
        _save_checkpoint()
        ckpt_s += time.perf_counter() - t0
        n_ckpts += 1
    if mgr is not None:
        mgr.wait()                      # durable before we report success
    wall_s = time.perf_counter() - t_start

    lost = tuple(sorted(set(prior_lost)
                        | set(getattr(reader, "lost_splits", ()))))
    # the survivors represent p·(valid/N) of the population; with no loss
    # valid == N exactly and this is the plain p (ratio is exactly 1.0).
    p_eff = p * (valid_rows / n_rows)
    stat = bind_params(spec, params)
    with trace.span("stream.finalize"):
        thetas = stat.correct(jax.vmap(stat.finalize)(states), p_eff)
        estimate = stat.correct(stat.finalize(est), p_eff)
        report = accuracy.report_for(
            thetas, alpha=alpha, num_groups=getattr(stat, "num_groups", None))
    return StreamingBootstrapResult(
        estimate=estimate, thetas=thetas, report=report,
        B=int(B), n=int(valid_rows),
        stream=StreamReport(wall_s=wall_s,
                            stage_s=timings.get("stage_s", 0.0),
                            wait_s=wait_s, dispatch_s=dispatch_s,
                            drain_s=drain_s, n_chunks=i - start_chunk,
                            rows=int(n_rows),
                            checkpoint_s=ckpt_s, n_checkpoints=n_ckpts,
                            resumed_from_chunk=resumed_from,
                            faults=counters, lost_splits=lost,
                            valid_rows=int(valid_rows)),
    )
