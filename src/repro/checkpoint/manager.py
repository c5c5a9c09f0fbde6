"""Checkpoint manager: atomic, async, keep-k, restore-with-resharding.

Layout (one directory per step, atomically renamed into place):

    <root>/ckpt_00001230/
        arrays.npz          flat {path -> array} of the state pytree
        meta.json           step, extra state (data-pipeline cursor, rng)

Restore takes a *template* pytree (e.g. from jax.eval_shape) and an
optional target sharding tree — restoring onto a different mesh is just
device_put with the new NamedShardings (the elastic-rescale path in
ft/elastic.py).  On a real multi-host cluster each host would write its
address-space shards (orbax-style); the format and the atomic-commit /
keep-k / async logic here are the substrate that sits under that.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import jax
import numpy as np

SEP = "/"

#: a staging dir older than this is reaped even if its pid LOOKS alive —
#: an in-flight _write is seconds old, so a "live" owner this stale is a
#: recycled pid, not a peer mid-write (pid reuse would otherwise pin a
#: crashed writer's garbage forever).
STALE_TMP_S = 3600.0


def _flatten(tree: Any) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = jax.tree_util.keystr(path)
        flat[key] = np.asarray(jax.device_get(leaf))
    return flat


def _unflatten_into(template: Any, flat: Dict[str, np.ndarray]) -> Any:
    paths = jax.tree_util.tree_flatten_with_path(template)[0]
    treedef = jax.tree_util.tree_structure(template)
    leaves = []
    for path, leaf in paths:
        key = jax.tree_util.keystr(path)
        if key not in flat:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"shape mismatch for {key}: "
                             f"{arr.shape} vs {leaf.shape}")
        leaves.append(arr.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def cursor(kind: str, fingerprint: str, **fields) -> Dict[str, Any]:
    """The ``extra`` of a resumable run's snapshot, for ``save``: the
    cursor with the run's ``kind``, its ``fingerprint`` and its own
    JSON-plain ``fields``, which ``CheckpointManager.restore_cursor`` checks
    and hands back."""
    return {"cursor": {"kind": kind, "fingerprint": fingerprint, **fields}}


class CheckpointManager:
    def __init__(self, root: str, keep_last: int = 3,
                 async_save: bool = True):
        self.root = root
        self.keep_last = keep_last
        self.async_save = async_save
        self._pool = ThreadPoolExecutor(max_workers=1)
        self._pending: Optional[Future] = None
        self._closed = False
        os.makedirs(root, exist_ok=True)
        self._gc_orphans()

    @classmethod
    def for_run(cls, root: str, fingerprint: str,
                keep_last: int = 3, async_save: bool = True
                ) -> "CheckpointManager":
        """A manager scoped to ONE run under a shared ``root``.

        Several standing sessions can point at the same checkpoint
        directory; without scoping they would overwrite each other's
        ``ckpt_<step>`` dirs (step counters collide) and keep-k GC would
        reap a peer's snapshots.  Scoping by the run fingerprint gives
        each distinct run its own subdirectory — same fingerprint, same
        subdirectory, so resume finds its own snapshots by construction.
        """
        return cls(os.path.join(root, f"run_{fingerprint[:16]}"),
                   keep_last=keep_last, async_save=async_save)

    @classmethod
    def open(cls, checkpoint, scope: Optional[str] = None,
             resume: bool = False) -> Optional["CheckpointManager"]:
        """A ``checkpoint=`` argument as a manager: a root path opens one
        with async saves (scoped by ``for_run`` to a run fingerprint
        ``scope``, if given); a manager or None passes through."""
        if resume and checkpoint is None:
            raise ValueError("resume=True needs checkpoint= (where would "
                             "the cursor come from?)")
        if not isinstance(checkpoint, (str, os.PathLike)):
            return checkpoint
        if scope is not None:
            return cls.for_run(checkpoint, scope)
        return cls(checkpoint, async_save=True)

    @staticmethod
    def _pid_alive(pid_s: str) -> bool:
        """Liveness of a pid string from a staging-dir name.  Anything
        unparseable or out of range has no live owner claim — treating it
        as dead is what lets GC make progress instead of skipping forever
        (a huge bogus pid used to raise OverflowError out of listdir)."""
        try:
            pid = int(pid_s)
        except ValueError:
            return False
        if pid <= 0:
            return False
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return False        # owner is gone: orphaned
        except PermissionError:
            return True         # pid exists under another uid: assume live
        except (OverflowError, ValueError):
            return False        # absurd pid: no live owner claim
        return True

    def _gc_orphans(self) -> None:
        """Remove stale write debris: ``.tmp_ckpt_*`` staging directories
        (a crash during ``_write``) and ``ckpt_*.old.*`` backup directories
        (a crash during the commit swap).  Neither holds a committed
        checkpoint, so leftovers would otherwise accumulate forever.

        Both name forms carry the writer's pid; a dir whose writer is
        still ALIVE belongs to a concurrent peer mid-write and is spared —
        unless it is older than ``STALE_TMP_S``: an in-flight write is
        seconds old, so a stale "live" owner is a recycled pid and the dir
        is reaped (the stale-pid regression, tests/test_checkpoint_ft.py).
        Suffixless/unparseable names have no live owner claim and are
        reaped.
        """
        for name in os.listdir(self.root):
            staging = name.startswith(".tmp_ckpt_")
            backup = name.startswith("ckpt_") and ".old." in name
            if not (staging or backup):
                continue
            path = os.path.join(self.root, name)
            if self._pid_alive(name.rpartition(".")[2]):
                try:
                    age = time.time() - os.path.getmtime(path)
                except OSError:
                    continue    # raced with its owner's rename/cleanup
                if age < STALE_TMP_S:
                    continue    # a live peer's in-flight write
            shutil.rmtree(path, ignore_errors=True)

    # -- lifecycle --------------------------------------------------------
    def close(self) -> None:
        """Flush the pending async write and shut the executor down."""
        if self._closed:
            return
        try:
            self.wait()
        finally:
            self._closed = True
            self._pool.shutdown(wait=True)

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- save -------------------------------------------------------------
    def _write(self, step: int, flat: Dict[str, np.ndarray],
               extra: Dict[str, Any]) -> str:
        # pid-suffixed staging name: a concurrent manager sharing this root
        # can tell a LIVE peer's in-flight write from a crashed one's
        # leftovers (see _gc_orphans).
        tmp = os.path.join(self.root, f".tmp_ckpt_{step:08d}.{os.getpid()}")
        final = os.path.join(self.root, f"ckpt_{step:08d}")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        try:
            with open(os.path.join(tmp, "arrays.npz"), "wb") as f:
                np.savez(f, **flat)
                f.flush()
                os.fsync(f.fileno())
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump({"step": step, "extra": extra}, f)
                f.flush()
                os.fsync(f.fileno())
        except BaseException:
            # ENOSPC / partial write: remove the half-written staging dir
            # and raise loudly.  ``final`` was never touched, so whatever
            # checkpoint existed before this save is still loadable.
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        # commit by swap, never by delete-then-rename: if this process
        # dies between the renames, the old snapshot survives in the
        # pid-suffixed backup (reaped by _gc_orphans once we are dead)
        # instead of having been rmtree'd before the new one landed.
        backup = None
        if os.path.exists(final):
            backup = f"{final}.old.{os.getpid()}"
            if os.path.exists(backup):
                shutil.rmtree(backup)
            os.rename(final, backup)
        os.rename(tmp, final)           # atomic commit
        if backup is not None:
            shutil.rmtree(backup, ignore_errors=True)
        self._gc()
        return final

    def save(self, step: int, state: Any,
             extra: Optional[Dict[str, Any]] = None) -> None:
        """Snapshot on the caller thread (device_get), write async."""
        flat = _flatten(state)           # synchronous snapshot
        extra = extra or {}
        self.wait()
        if self.async_save:
            self._pending = self._pool.submit(self._write, step, flat, extra)
        else:
            self._write(step, flat, extra)

    def wait(self) -> None:
        if self._pending is not None:
            self._pending.result()
            self._pending = None

    # -- restore ----------------------------------------------------------
    def steps(self) -> List[int]:
        out = []
        for name in os.listdir(self.root):
            # skip anything that merely LOOKS like a checkpoint (stray
            # files, hand-made dirs like "ckpt_old") instead of raising —
            # a foreign entry must not brick every restore under this root.
            if not name.startswith("ckpt_"):
                continue
            suffix = name[len("ckpt_"):]
            if not suffix.isdigit():
                continue
            if not os.path.isdir(os.path.join(self.root, name)):
                continue
            out.append(int(suffix))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        s = self.steps()
        return s[-1] if s else None

    def meta(self, step: Optional[int] = None) -> Dict[str, Any]:
        """The ``extra`` dict of the given (default: latest) checkpoint,
        without loading the arrays — resume paths validate the cursor
        (fingerprint etc.) BEFORE committing to an array restore, so a
        wrong-run checkpoint fails with the right diagnostic instead of a
        shape mismatch."""
        self.wait()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.root}")
        d = os.path.join(self.root, f"ckpt_{step:08d}")
        with open(os.path.join(d, "meta.json")) as f:
            return json.load(f)["extra"]

    def restore(self, template: Any, step: Optional[int] = None,
                shardings: Any = None) -> tuple[Any, Dict[str, Any]]:
        """Restore into ``template``'s structure; optionally re-place onto
        ``shardings`` (a pytree of NamedSharding — the elastic path)."""
        self.wait()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.root}")
        d = os.path.join(self.root, f"ckpt_{step:08d}")
        with np.load(os.path.join(d, "arrays.npz")) as z:
            flat = {k: z[k] for k in z.files}
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        state = _unflatten_into(template, flat)
        if shardings is not None:
            state = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s), state, shardings)
        else:
            state = jax.tree_util.tree_map(jax.numpy.asarray, state)
        return state, meta["extra"]

    def restore_cursor(self, kind: str, fingerprint: str, template: Any,
                       what: str, binds: str) -> tuple[Any, Dict[str, Any]]:
        """The latest ``cursor`` snapshot of a ``kind`` run, as
        ``(carry, cursor)``.

        The cursor is checked before any array is read, so a checkpoint of
        another kind (``what`` names this one) or of another run (whose
        fingerprint differs in ``binds``) fails with its own message, not a
        shape mismatch.  ``template``: the carry's shapes, or a function of
        the cursor that gives them.  A cursor without a kind is a stream
        pass's, as the stream pass wrote them before cursors had kinds.
        """
        cur = self.meta().get("cursor")
        if cur is None or cur.get("kind", "stream") != kind:
            raise ValueError(f"checkpoint under {self.root} has no {what} "
                             f"cursor — not a {what} checkpoint")
        if cur["fingerprint"] != fingerprint:
            raise ValueError(
                "checkpoint fingerprint mismatch: the snapshot was taken "
                f"under a different {binds} — resuming it would silently "
                "produce a different estimator "
                f"(checkpoint {cur['fingerprint'][:12]}…, "
                f"run {fingerprint[:12]}…)")
        if callable(template):
            template = template(cur)
        carry, _ = self.restore(template)
        return carry, cur

    # -- gc ---------------------------------------------------------------
    def _gc(self) -> None:
        steps = self.steps()
        for s in steps[:-self.keep_last]:
            shutil.rmtree(os.path.join(self.root, f"ckpt_{s:08d}"),
                          ignore_errors=True)
