"""The tracer — a lock-light per-process ring buffer of events.

Recording discipline (the SPC pattern, SURVEY.md §5(d)): every in-path
hook is guarded by the module-level ``_enabled`` boolean, so a build
with tracing off (the default) pays exactly one attribute test per
hook — the only cost tracing adds to an untraced run.  When enabled,
an event append is one tuple construction plus a ``deque.append``
(atomic under the GIL) and one short critical section updating the
per-(layer, op) aggregates — required because transport receiver
threads record dcn/p2p spans concurrently with the main thread's api
spans, and the pvar counters must match the ring's census exactly.

Event model (≈ the Chrome trace-event phases this maps onto):

* **span** (``ph="X"``): opened by :func:`span` and closed by
  :meth:`Span.end` or the end of its ``with`` block — one ring record
  per span, written when it closes;
* **instant** (``ph="i"``): a point event (an algorithm decision, a
  protocol choice).

Two sinks.  Every span goes to the ring; while a ``jax.profiler``
session records, the same span also goes to the profiler's timeline as
a TraceMe named ``ompi.<layer>.<name>``, opened and closed at the same
points as the ring record, with its comm, seq and args as the event's
metadata.  There it shares the clock of the device planes, so one
profile shows the library's host time beside the chip's work.  The
binding (``jax.profiler.TraceAnnotation``) is imported on the first
``enable(True)``, so this module imports without jax.

Collective spans carry a ``(comm, op, seq)`` key: ``seq`` is a
per-(comm, op) issue counter.  MPI's same-issue-order rule makes the
counter identical on every rank, so the key aligns one rank's span
with its peers' in a cross-rank merge (:mod:`ompi_tpu.trace.merge`)
— the role the reference's sequence numbers play in ob1 matching,
reused for observability.

Timestamps are ``time.perf_counter_ns()`` (monotonic); export anchors
them to the wall-clock epoch captured at enable time so per-process
traces from one host land on a shared timeline.
"""

from __future__ import annotations

import collections
import threading
import time

#: the in-path gate — hooks read this attribute directly
_enabled = False

_DEFAULT_BUFFER = 65536

_events: collections.deque = collections.deque(maxlen=_DEFAULT_BUFFER)
_dropped = 0
_seq_lock = threading.Lock()
_seqs: dict[tuple[str, str], int] = {}
#: cumulative per-(layer, op) span aggregates, updated at append time —
#: O(1) per span, independent of ring eviction (counters never go
#: backwards when the buffer wraps).  Insertion-ordered and grow-only
#: while tracing runs: the MPI_T pvar namespace indexes into it, and
#: C-side pvar handles cache indices, so keys are only ever APPENDED
#: (reset zeroes values in place; see :func:`reset`).
_stats: dict[tuple[str, str], dict] = {}
#: wall-clock anchor: (time_ns, perf_counter_ns) captured at enable
_epoch: tuple[int, int] = (0, 0)
#: the profiler sink, ``jax.profiler.TraceAnnotation`` (a jaxlib TraceMe);
#: bound on the first enable(True), stays None where jax is absent
_TraceMe = None

#: histogram buckets: log2 of the span duration in µs; bucket i holds
#: spans with 2**(i-1) µs <= dur < 2**i µs (bucket 0: sub-µs), the
#: last bucket is open-ended.
HIST_BUCKETS = 16


def enabled() -> bool:
    return _enabled


def enable(flag: bool = True, buffer_events: int | None = None) -> None:
    """Turn tracing on/off (tests and the MPI_T surface; production
    jobs go through ``--mca trace_enable 1`` → :func:`sync_from_store`)."""
    global _enabled, _events, _epoch, _TraceMe
    if buffer_events is not None and buffer_events != _events.maxlen:
        _events = collections.deque(_events, maxlen=max(1, int(buffer_events)))
    if flag and not _enabled:
        _epoch = (time.time_ns(), time.perf_counter_ns())
        if _TraceMe is None:
            try:
                from jax.profiler import TraceAnnotation as _TraceMe
            except ImportError:
                pass
    _enabled = flag


def reset(seqs: bool = True) -> None:
    """Drop recorded events, the drop count, and span aggregates.

    ``seqs=False`` (the MPI_T pvar_reset path) keeps the per-(comm,
    op) issue counters — resetting those mid-run would desynchronize
    the cross-rank merge keys of later collectives — and zeroes the
    span aggregates IN PLACE instead of dropping them: the pvar
    namespace (and C-side pvar handles caching indices into it) must
    not shrink under a live tool session."""
    global _dropped
    with _seq_lock:
        _events.clear()
        _dropped = 0
        if seqs:
            _seqs.clear()
            _stats.clear()
        else:
            for st in _stats.values():
                st["count"] = 0
                st["total_ns"] = 0
                st["max_ns"] = 0
                st["hist"] = [0] * HIST_BUCKETS


def next_seq(comm: str, op: str) -> int:
    """Per-(comm, op) issue counter — the cross-rank merge key.
    Identical on every rank by MPI's same-issue-order rule."""
    key = (comm, op)
    with _seq_lock:
        s = _seqs.get(key, 0)
        _seqs[key] = s + 1
        return s


def _append(ev: tuple) -> None:
    global _dropped
    if len(_events) == _events.maxlen:
        _dropped += 1  # benign race: diagnostic counter
    _events.append(ev)


def _record(layer: str, name: str, t0_ns: int, dur: int, comm: str,
            seq: int, args: dict | None) -> None:
    _append(("X", t0_ns, dur, layer, name, comm, seq, args))
    # the aggregate update is a read-modify-write reached from multiple
    # threads (transport recv threads record p2p/dcn spans concurrently
    # with the main thread's api spans), so it takes the lock — only on
    # the enabled path, and the pvar counters must match the ring's
    # event census exactly (the cross-check the subsystem advertises)
    with _seq_lock:
        st = _stats.get((layer, name))
        if st is None:
            st = _stats[(layer, name)] = {
                "count": 0, "total_ns": 0, "max_ns": 0,
                "hist": [0] * HIST_BUCKETS,
            }
        st["count"] += 1
        st["total_ns"] += dur
        if dur > st["max_ns"]:
            st["max_ns"] = dur
        st["hist"][min((dur // 1000).bit_length(), HIST_BUCKETS - 1)] += 1


class Span:
    """One open span; see :func:`span`.  ``args`` may grow until it
    closes (``sp.args[...]``, :meth:`end` keyword arguments)."""

    __slots__ = ("layer", "name", "comm", "seq", "args", "t0", "_tm")

    def __init__(self, layer: str, name: str, comm: str, seq: int,
                 args: dict):
        self.layer, self.name, self.comm, self.seq = layer, name, comm, seq
        self.args = args
        tm = _TraceMe
        if tm is not None and tm.is_enabled():  # a profiler session records
            tm = tm(f"ompi.{layer}.{name}")
            tm.__enter__()
        else:
            tm = None
        self._tm = tm
        self.t0 = time.perf_counter_ns()

    def end(self, **args) -> None:
        """Close the span: the profiler event, and one ring record unless
        tracing was turned off while it was open."""
        if args:
            self.args.update(args)
        tm = self._tm
        if tm is not None:
            meta = dict(self.args)
            if self.comm:
                meta["comm"] = self.comm
            if self.seq >= 0:
                meta["seq"] = self.seq
            tm.set_metadata(**meta)
        dur = time.perf_counter_ns() - self.t0
        if tm is not None:
            tm.__exit__(None, None, None)
        if _enabled:
            _record(self.layer, self.name, self.t0, dur, self.comm,
                    self.seq, self.args or None)

    def child(self, layer: str, name: str, **args) -> "Span":
        """Open a span of a layer below, inside this one: it shares this
        span's ``seq`` and takes no ``comm``, so only the outer span
        carries the call's merge key."""
        return Span(layer, name, "", self.seq, args)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


def span(layer: str, name: str, comm: str = "", seq: int = -1,
         **args) -> Span:
    """Open a span now: ``with span(...) as sp:``, or ``sp = span(...)``
    then ``sp.end(**more_args)`` in a ``finally``.  Call sites test the module's on-flag
    first (``sp = _trace.span(...) if <flag> else None``), so tracing off
    costs one boolean test per hook and builds nothing.  ``comm`` and
    ``seq`` (from :func:`next_seq`) give a collective's api span its
    cross-rank merge key; :meth:`Span.child` opens the spans below it."""
    return Span(layer, name, comm, seq, args)


def instant(layer: str, name: str, comm: str = "", **args) -> None:
    """Record a point event (decision, protocol choice, milestone)."""
    if not _enabled:
        return
    _append(("i", time.perf_counter_ns(), 0, layer, name, comm, -1,
             args or None))


def wrap_call(layer: str, name: str, fn, comm: str = "", **args):
    """Closure recording one span around each ``fn(*a, **k)`` call —
    used where a dispatch layer hands out a callable (coll-table
    lookups).  Collective api-layer wraps get a fresh seq per call."""
    keyed = layer == "api"

    def traced(*a, **k):
        with span(layer, name, comm, next_seq(comm, name) if keyed else -1,
                  **args):
            return fn(*a, **k)

    traced.__name__ = f"traced_{name}"
    traced.__wrapped__ = fn
    return traced


# -- introspection ------------------------------------------------------


def events() -> list[tuple]:
    """Snapshot of the ring buffer (oldest first)."""
    return list(_events)


def event_count() -> int:
    return len(_events)


def dropped() -> int:
    return _dropped


def epoch() -> tuple[int, int]:
    """(wall-clock ns, perf_counter ns) anchor captured at enable."""
    return _epoch


def span_stats() -> dict[tuple[str, str], dict]:
    """Cumulative per-(layer, op) span aggregates: count, total_ns,
    max_ns, and the log2-µs latency histogram — the MPI_T pvar source.
    Maintained incrementally at record time (no ring scan) and keyed
    by layer so p2p 'send' and dcn 'send' never conflate."""
    return {k: dict(v, hist=list(v["hist"])) for k, v in _stats.items()}


def span_ops() -> list[tuple[str, str]]:
    """(layer, op) pairs with ≥1 recorded span, in FIRST-SEEN order —
    the pvar namespace.  Grow-only while tracing runs (reset zeroes in
    place), so pvar indices cached by C tool handles stay valid."""
    return list(_stats)


def span_count(layer: str, op: str) -> int:
    """Span count for one (layer, op) — O(1), no stats-table copy."""
    st = _stats.get((layer, op))
    return st["count"] if st else 0


def latency_histogram(layer: str, op: str) -> list[int]:
    """Log2-µs duration histogram for one (layer, op); zeros if unseen."""
    st = _stats.get((layer, op))
    return list(st["hist"]) if st else [0] * HIST_BUCKETS


def zero_stats() -> None:
    """Zero every span aggregate and the drop counter IN PLACE, keeping
    the event ring, the seq counters, and the pvar namespace — the
    MPI_T session-wide pvar_reset: counters restart, but the recorded
    TIMELINE survives to the finalize-time trace file (same invariant
    the per-handle reset enforces by refusing ``trace_events``)."""
    global _dropped
    with _seq_lock:
        _dropped = 0
        for st in _stats.values():
            st["count"] = 0
            st["total_ns"] = 0
            st["max_ns"] = 0
            st["hist"] = [0] * HIST_BUCKETS


def reset_span_stat(layer: str, op: str) -> None:
    """Zero ONE (layer, op) aggregate in place (MPI_T pvar_reset on a
    single handle); the key stays registered — index stability."""
    st = _stats.get((layer, op))
    if st is not None:
        st["count"] = 0
        st["total_ns"] = 0
        st["max_ns"] = 0
        st["hist"] = [0] * HIST_BUCKETS


def reset_dropped() -> None:
    global _dropped
    _dropped = 0


# -- MCA wiring (≈ memchecker's register_var/sync_from_store pattern) ---


def register_vars(store) -> None:
    """Delegates to the central observability table (core.var) — one
    source of truth for names/defaults/descriptions, and the vars show
    in ``--mca``-var listings even before this module imports."""
    from ompi_tpu.core.var import register_observability_vars

    register_observability_vars(store)


def sync_from_store(store) -> None:
    enable(
        bool(store.get("trace_enable", False)),
        buffer_events=int(store.get("trace_buffer_events", _DEFAULT_BUFFER)),
    )
