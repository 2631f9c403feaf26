"""Event tracing — cross-layer timeline introspection.

The third observability leg next to SPC counters (aggregates) and the
monitoring matrices (per-peer totals): a per-rank timeline of *spans*
showing where a microsecond went inside one operation as it crosses
api → coll → pml → dcn (SURVEY.md §5(c)–(d) name the first two legs;
the reference's per-event story is MPI_T pvars + external PMPI tracers
— here the tracer is in-tree and exports Chrome trace-event JSON).

Layout:

* :mod:`ompi_tpu.trace.core` — the tracer itself: a lock-light ring
  buffer of events, gated by ``--mca trace_enable 1`` (default off:
  one boolean check in-path, the SPC pattern); while on, each span
  also lands on any running ``jax.profiler`` trace as
  ``ompi.<layer>.<name>``, on the device trace's clock;
* :mod:`ompi_tpu.trace.chrome` — Chrome trace-event JSON export
  (``chrome://tracing`` / Perfetto loadable);
* :mod:`ompi_tpu.trace.merge` — cross-rank merge of per-process trace
  files into one timeline, collective spans keyed by (comm, op, seq).

Everything here imports without jax (the profiler binding loads on the
first ``enable(True)``), so ``tools/trace_report.py`` can load and
merge traces where jax is absent.
"""

from .core import (  # noqa: F401
    dropped,
    enable,
    enabled,
    event_count,
    events,
    instant,
    latency_histogram,
    next_seq,
    register_vars,
    reset,
    span,
    span_stats,
    sync_from_store,
    wrap_call,
)
