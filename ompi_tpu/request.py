"""Request engine — completion objects for non-blocking operations.

TPU-native re-design of ``ompi/request/`` (symbols
``ompi_request_default_wait_all``, ``ompi_request_functions`` [bin];
SURVEY.md §2.1, §3.4).  The reference's request is a state machine
advanced by ``opal_progress`` polling transport callbacks; here the
XLA runtime IS the progress engine — dispatch is asynchronous, every
output is a future-like ``jax.Array``, and

* ``wait``  ≈ ``MPI_Wait``  → ``jax.block_until_ready``
* ``test``  ≈ ``MPI_Test``  → ``jax.Array.is_ready()``

``libnbc``'s compiled round-schedules (NBC_Sched_create/NBC_Progress)
collapse into the XLA program itself: the whole collective is one
dispatched computation, so a request holds its outputs, not a schedule
position.  Persistent requests (MPI_*_init/MPI_Start, the ≥5.0 API)
hold the compiled callable and re-dispatch on ``start()``.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import jax

from ompi_tpu.core.errors import MPIRequestError
from ompi_tpu.metrics import core as _metrics
from ompi_tpu.trace import core as _trace


class Request:
    """Base non-blocking request (≈ ompi_request_t)."""

    def __init__(self):
        self._complete = False
        self._result: Any = None
        self._cancelled = False

    # -- subclass hooks -------------------------------------------------

    def _poll(self) -> bool:
        """Return True if the underlying work finished (non-blocking)."""
        return True

    def _finalize(self) -> Any:
        """Produce the user-visible result; called once on completion."""
        return self._result

    # -- MPI surface ----------------------------------------------------

    def test(self) -> bool:
        """MPI_Test: non-blocking completion check."""
        if self._complete:
            return True
        if self._poll():
            self._result = self._finalize()
            self._complete = True
        return self._complete

    def wait(self) -> Any:
        """MPI_Wait: block until complete, return the operation result."""
        if not self._complete:
            t0 = time.perf_counter_ns() if _metrics._enabled else 0
            # the blocked-completion span: where caller time goes while
            # the fabric/DCN works (stragglers)
            sp = _trace.span("request", f"{type(self).__name__}.wait") \
                if _trace._enabled else None
            try:
                self._block()
                self._result = self._finalize()
                self._complete = True
            finally:
                if sp is not None:
                    sp.end()
            if t0:
                # same blocked time as a latency histogram — the
                # quantitative view (p50/p99 without a trace run)
                _metrics.observe(
                    f"request_wait_{type(self).__name__}", 0,
                    time.perf_counter_ns() - t0)
        return self._result

    def _block(self) -> None:
        while not self._poll():  # pragma: no cover - subclasses override
            time.sleep(0)

    def cancel(self) -> None:
        """MPI_Cancel: best-effort; XLA dispatch cannot be revoked, so
        like the reference's completed-request case this is a no-op once
        work is in flight."""
        self._cancelled = True

    @property
    def completed(self) -> bool:
        return self._complete

    def free(self) -> None:
        self._result = None


class CompletedRequest(Request):
    """Immediately-complete request (host-path collectives, empty ops)."""

    def __init__(self, result: Any = None):
        super().__init__()
        self._complete = True
        self._result = result


class ArrayRequest(Request):
    """Request over async-dispatched jax arrays (the coll/xla i-path).

    ``finalize`` post-processes the ready arrays (e.g. D2H unpack into
    the caller's buffer) exactly once.
    """

    def __init__(self, arrays: Sequence[jax.Array] | jax.Array, finalize: Callable[[Any], Any] | None = None):
        super().__init__()
        self._arrays = arrays
        self._user_finalize = finalize

    def _leaves(self):
        return jax.tree_util.tree_leaves(self._arrays)

    def _poll(self) -> bool:
        return all(a.is_ready() for a in self._leaves())

    def _block(self) -> None:
        for a in self._leaves():
            jax.block_until_ready(a)

    def _finalize(self) -> Any:
        if self._user_finalize is not None:
            return self._user_finalize(self._arrays)
        return self._arrays


class FutureRequest(Request):
    """Request over work progressing on a background thread — the
    libnbc model (SURVEY.md §3.4): the host/DCN half of a hierarchical
    collective runs off the caller's thread, so caller compute overlaps
    communication.  Wraps a ``concurrent.futures.Future``; a failure in
    the background collective re-raises at wait()/test() completion,
    matching the reference's error-on-completion semantics."""

    def __init__(self, future):
        super().__init__()
        self._future = future

    def _poll(self) -> bool:
        return self._future.done()

    def _block(self) -> None:
        self._future.exception()  # waits without raising; _finalize raises

    def _finalize(self) -> Any:
        return self._future.result()


class PersistentRequest(Request):
    """MPI persistent collective (MPI_Allreduce_init → MPI_Start →
    MPI_Wait, repeatable).  Holds the compiled dispatcher; ``start()``
    launches a fresh round."""

    def __init__(self, dispatch: Callable[[], Request]):
        super().__init__()
        self._dispatch = dispatch
        self._active: Request | None = None
        self._complete = True  # inactive persistent requests are "complete"

    def start(self) -> "PersistentRequest":
        if self._active is not None and not self._active.completed:
            raise MPIRequestError("persistent request started while active")
        self._active = self._dispatch()
        self._complete = False
        return self

    def _poll(self) -> bool:
        return self._active is None or self._active.test()

    def _block(self) -> None:
        if self._active is not None:
            self._active.wait()

    def _finalize(self) -> Any:
        return None if self._active is None else self._active.wait()

    @property
    def status(self):
        """Envelope of the most recent round (persistent recv)."""
        return getattr(self._active, "status", None)


# -- wait/test families (MPI_Waitall etc.) -----------------------------


def waitall(requests: Sequence[Request]) -> list[Any]:
    return [r.wait() for r in requests]


def testall(requests: Sequence[Request]) -> bool:
    return all(r.test() for r in requests)


def _poll_backoff(sleep: float) -> float:
    """Exponential poll backoff (0 → 50µs → … → 1ms cap): avoids
    burning the controller core while the fabric works."""
    time.sleep(sleep)
    return min(max(sleep * 2, 50e-6), 1e-3)


def waitany(requests: Sequence[Request]) -> tuple[int, Any]:
    """Block until at least one completes; returns (index, result)."""
    if not requests:
        raise MPIRequestError("waitany on empty request list")
    if len(requests) == 1:
        return 0, requests[0].wait()
    sleep = 0.0
    while True:
        for i, r in enumerate(requests):
            if r.test():
                return i, r.wait()
        sleep = _poll_backoff(sleep)


def testany(requests: Sequence[Request]) -> tuple[int, Any] | None:
    for i, r in enumerate(requests):
        if r.test():
            return i, r.wait()
    return None


def waitsome(requests: Sequence[Request]) -> list[tuple[int, Any]]:
    """Block until ≥1 complete; return all completed (index, result)."""
    if not requests:
        return []
    sleep = 0.0
    while True:
        done = [(i, r.wait()) for i, r in enumerate(requests) if r.test()]
        if done:
            return done
        sleep = _poll_backoff(sleep)
