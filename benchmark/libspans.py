"""The library's own spans in a profiler trace, and the split of each
call's host round trip that they give.

While ``ompi_tpu``'s tracing is on (``--mca trace_enable 1`` or
``ompi_tpu.trace.enable()``), every span it records also lands on the
profiler's host plane as ``ompi.<layer>.<op>``, with the span's args as
the event's stats, on the clock of the chips' ``XLA Ops``.  On the
device fast path of a collective a call is one ``ompi.api.<op>`` (args
``seq``, ``nbytes``, ``hot``, ``recycled``) holding ``ompi.coll.launch``
(the compiled program's call) and, when the api's program cache misses,
``ompi.coll.resolve``, which holds ``ompi.coll.build`` when the coll
program cache builds the program.

How a deployment's spans reach its readers: ``harness.run`` records,
after the traced window, a second profiler session of the same schedule
with the library's tracing on, and hands the readers that window as
``run.lib``, the ``(tr, lib)`` pair that the functions here take
(``tr`` from ``trace.from_profile``, ``lib`` from ``from_profile``).
Which api span is the deployment's is its call module's ``API_SPAN``;
a module without one gets no span numbers.  So a deployment brings its
span numbers by files alone: its call module names its span, and a
reader in ``benchmark/metrics`` calls a function here.  Every number
here is taken inside ``bench.window``; each is None where the trace
holds no library spans.
"""

from __future__ import annotations

import bisect
import collections
import statistics

from benchmark import trace as trace_mod

LIB_SPAN = "ompi."
LAUNCH = "ompi.coll.launch"
CHILD = "ompi.coll."
WAIT = "bench.wait"


def from_profile(pd) -> dict[str, list[tuple[float, float, dict]]]:
    """The library's host-plane events by name, as (start, end, stats)
    in nanoseconds on the profiler's clock, in start order."""
    out = collections.defaultdict(list)
    for plane in pd.planes:
        if plane.name != trace_mod.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(LIB_SPAN):
                    out[e.name].append((e.start_ns, e.start_ns + e.duration_ns,
                                        {k: v for k, v in e.stats}))
    return {k: sorted(v, key=lambda s: s[:2]) for k, v in out.items()}


def of_run(run) -> tuple | None:
    """``(tr, lib, api)`` for a reader: the run's second traced window
    and its call module's ``API_SPAN``; None where the run has no such
    window or the module names no api span."""
    api = getattr(run.call, "API_SPAN", None)
    return None if run.lib is None or api is None else (*run.lib, api)


def _inside(tr: trace_mod.Trace, spans) -> list:
    return [s for s in spans if s[0] >= tr.lo and s[1] <= tr.hi]


def _us(ns: float) -> float:
    return ns / 1e3


# -- the per-call numbers ------------------------------------------------------

def api_self_us(tr: trace_mod.Trace, lib: dict, api: str) -> float | None:
    """Median over calls of the api span ``api``'s time outside its coll
    children: the api layer's own Python."""
    calls = _inside(tr, lib.get(api, []))
    if not calls:
        return None
    kids = sorted((s, e) for k, v in lib.items() if k.startswith(CHILD)
                  for s, e, _ in v)
    starts = [s for s, _ in kids]
    selfs = []
    for s, e, _ in calls:
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
        selfs.append(e - s - trace_mod.union_ns(kids[lo:hi], s, e))
    return _us(statistics.median(selfs))


def api_hot_share(tr: trace_mod.Trace, lib: dict, api: str) -> float | None:
    """Share of api calls that the last-signature cache served, %."""
    calls = _inside(tr, lib.get(api, []))
    if not calls:
        return None
    return 100.0 * sum(1 for *_, st in calls if st.get("hot") == 1) / len(calls)


def recycle_hit_share(tr: trace_mod.Trace, lib: dict,
                      api: str) -> float | None:
    """Share of api calls whose result went into a dropped earlier one
    (``recycled`` 1: the arena's spare pool served it), %; None where no
    call carries the arg."""
    calls = _inside(tr, lib.get(api, []))
    if not any("recycled" in st for *_, st in calls):
        return None
    return 100.0 * sum(1 for *_, st in calls
                       if st.get("recycled") == 1) / len(calls)


def launch_us(tr: trace_mod.Trace, lib: dict) -> float | None:
    """Median time in the call of the compiled program."""
    launches = _inside(tr, lib.get(LAUNCH, []))
    if not launches:
        return None
    return _us(statistics.median(e - s for s, e, _ in launches))


def _ops(tr: trace_mod.Trace) -> dict[int, list[tuple[float, float]]]:
    """Each chip's ``XLA Ops`` events as (start, end), copies left out."""
    copies = set(trace_mod.TRANSFERS.values())
    return {chip: [(s, e) for s, e, name in ops if name not in copies]
            for chip, ops in tr.devices.items()}


def launch_to_device_us(tr: trace_mod.Trace, lib: dict) -> float | None:
    """Per call: on each chip, from the launch's start to the start of
    the first operation at or after it and before the next call's
    launch; averaged over the chips, then the median over calls."""
    launches = [s for s, _, _ in _inside(tr, lib.get(LAUNCH, []))]
    per_chip = [[s for s, _ in ops] for ops in _ops(tr).values()]
    if not launches or not per_chip:
        return None
    waits = []
    for i, t in enumerate(launches):
        nxt = launches[i + 1] if i + 1 < len(launches) else tr.hi
        gaps = []
        for starts in per_chip:
            j = bisect.bisect_left(starts, t)
            if j < len(starts) and starts[j] < nxt:
                gaps.append(starts[j] - t)
        if gaps:
            waits.append(sum(gaps) / len(gaps))
    return _us(statistics.median(waits)) if waits else None


def clock_offset_us(tr: trace_mod.Trace, lib: dict) -> list[float] | None:
    """Bounds on how far the chips' clock in the trace runs ahead of
    the host's (negative: behind), from causality alone: no call's op
    starts before its launch (``bench.call`` without library spans) and
    none ends after its ``bench.wait``.  Numbers that compare the two
    clocks (``launch_to_device_us``, the ``bench.wait`` split) are exact
    only where the bounds hold 0 tightly.  None unless every chip holds
    one op per call."""
    waits = [e for _, e in tr.spans.get(WAIT, [])]
    starts = [s for s, _, _ in _inside(tr, lib.get(LAUNCH, []))] or [
        s for s, _ in tr.spans.get("bench.call", [])]
    lo, hi = -float("inf"), float("inf")
    for real in _ops(tr).values():
        if not real or not (len(real) == len(starts) == len(waits)):
            return None
        lo = max(lo, max(e - w for (_, e), w in zip(real, waits)))
        hi = min(hi, min(s - t for (s, _), t in zip(real, starts)))
    return None if hi == float("inf") else [_us(lo), _us(hi)]


# -- idle time by the innermost host span -----------------------------------------

def _segments(spans) -> list[tuple[float, float, int]]:
    """Cut the host timeline where any span starts or ends; each piece
    as (start, end, index of the innermost span covering it: the one
    that started last), pieces no span covers left out."""
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    by_start = sorted(range(len(spans)), key=lambda i: spans[i][0])
    out, active, k = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(by_start) and spans[by_start[k]][0] <= a:
            active.append(by_start[k])
            k += 1
        active = [i for i in active if spans[i][1] > a]
        if active:
            out.append((a, b, max(active, key=lambda i: (spans[i][0],
                                                         -spans[i][1]))))
    return out


def idle_by_span(tr: trace_mod.Trace, lib: dict | None = None,
                 top: int = 10) -> list[list]:
    """The chips' idle time (seconds, averaged over the chips) by the
    innermost host span it fell in, as ``Trace.breakdown``'s
    ``idle_gaps`` gives it by any span: ``ompi.*`` spans, the
    harness's ``bench.call`` outside them, ``bench.wait`` split into
    ``bench.wait.before_op`` and ``bench.wait.after_op`` (before or
    after that chip's operation for the same call), and
    ``between_calls``.  The total is the same as ``breakdown``'s."""
    spans = [(s, e, k) for k, v in tr.spans.items() if k != "bench.window"
             for s, e in v]
    spans += [(s, e, k) for k, v in (lib or {}).items() for s, e, _ in v]
    segs = _segments(spans)
    calls = [s for s, _ in tr.spans.get("bench.call", [])]
    op_starts = {chip: [s for s, _ in ops] for chip, ops in _ops(tr).items()}
    idle = collections.Counter()
    for chip, ops in tr.devices.items():
        j = 0
        for gs, ge in trace_mod.gaps_ns([(s, e) for s, e, _ in ops],
                                        tr.lo, tr.hi):
            covered = 0.0
            while j < len(segs) and segs[j][1] <= gs:
                j += 1
            m = j
            while m < len(segs) and segs[m][0] < ge:
                a, b, i = segs[m]
                part = min(b, ge) - max(a, gs)
                if part > 0:
                    label = spans[i][2]
                    if label == WAIT:
                        label = _wait_side(spans[i][0], max(a, gs), calls,
                                           op_starts[chip])
                    idle[label] += part
                    covered += part
                m += 1
            if ge - gs - covered > 0:
                idle["between_calls"] += ge - gs - covered
    k = max(len(tr.devices), 1)
    return [[n, v / k / 1e9] for n, v in idle.most_common(top)]


def _wait_side(wait_start, t, calls, starts) -> str:
    """Whether idle time at ``t`` in the wait that starts at
    ``wait_start`` lies before or after this chip's operation for the
    same call: the first operation that starts after the call did."""
    c = bisect.bisect_right(calls, wait_start) - 1
    if c < 0:
        return WAIT
    j = bisect.bisect_left(starts, calls[c])
    if j == len(starts):
        return WAIT
    return WAIT + (".before_op" if t < starts[j] else ".after_op")


def numbers(tr: trace_mod.Trace, lib: dict, api: str) -> dict:
    return {"api_self_us": api_self_us(tr, lib, api),
            "api_hot_share": api_hot_share(tr, lib, api),
            "recycle_hit_share": recycle_hit_share(tr, lib, api),
            "launch_us": launch_us(tr, lib),
            "launch_to_device_us": launch_to_device_us(tr, lib),
            "clock_offset_us": clock_offset_us(tr, lib)}
