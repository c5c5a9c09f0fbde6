"""The program's own spans and counters.

``span(name, **meta)`` marks a stretch of host work as ``earl.<name>`` in
the profiler's trace (``jax.profiler.TraceAnnotation``), on the same clock
as the device's operations; keyword arguments ride along as the span's
metadata (``chunk=i``, ``round=k``).  A span costs about a microsecond when
no profiler runs and reaches a trace only when one does, e.g.::

    jax.profiler.start_trace("/tmp/earl-trace")
    session.run(key)
    jax.profiler.stop_trace()

``count(name, n)`` adds to a process-wide counter, and ``counts()`` returns
a snapshot of all of them; callers read the difference over the work they
care about.  ``host_sync`` counts the reads of a device value on the host
(``to_host``) on the session and stream paths, each of which waits for the
device; ``session_run`` counts ``EarlSession.run`` calls, so that
``host_sync`` per session can be read without a snapshot around each.
On a mesh, ``mesh_folds`` counts the sharded folds the host dispatches
(each inside an ``earl.mesh.fold`` span), ``mesh_psum_bytes`` the bytes of
the state pytrees they psum, and ``mesh_traces`` the times JAX traces a
sharded fold's body, which a warm fold does not.
``result_steps`` counts the session's compiled result steps the host
dispatches: SSABE's B̂ ladder, each of its ``l`` n-steps and each σ round
(``poisson_delta_result``), 1 + l + rounds a session, each read back in one
``host_sync``; ``result_traces`` counts the times JAX traces their bodies,
which a warm query does not.
``estimate_in_kernel`` counts the times ``core.fold.fold_rows`` traces a
point estimate whose histogram counts come out of the fused group kernel,
and ``estimate_scatter`` those whose histogram ``update`` builds (a
scatter-add, or the histogram kernel of a Quantile's own backend); a warm
program adds to neither.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict

import jax
import numpy as np

PREFIX = "earl."
HOST_SYNC = "host_sync"
MESH_FOLDS = "mesh_folds"
MESH_TRACES = "mesh_traces"
MESH_PSUM_BYTES = "mesh_psum_bytes"
RESULT_STEPS = "result_steps"
RESULT_TRACES = "result_traces"
ESTIMATE_IN_KERNEL = "estimate_in_kernel"
ESTIMATE_SCATTER = "estimate_scatter"

_lock = threading.Lock()
_counts: Dict[str, int] = {}


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A context manager that marks its block as ``earl.<name>``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` (thread-safe)."""
    with _lock:
        _counts[name] = _counts.get(name, 0) + n


def counts() -> Dict[str, int]:
    """A snapshot of every counter."""
    with _lock:
        return dict(_counts)


def to_host(value, convert: Callable = np.asarray):
    """``convert(value)``, counted as one ``host_sync`` when ``value`` is a
    device array (the read then waits for the device)."""
    if isinstance(value, jax.Array):
        count(HOST_SYNC)
    return convert(value)
