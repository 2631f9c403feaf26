"""Reduction of a JAX profiler trace to per-layer numbers.

The trace (``<dir>/plugins/profile/<time>/*.xplane.pb``) is read with
``jax.profiler.ProfileData``.  Each TPU chip is a plane named
``/device:TPU:<i>``; its line ``XLA Ops`` holds one event per operation
the chip ran.  Copies between host and chip are not operations there:
they are events of the host plane ``/host:CPU``, where a copy starts
with ``tpu::System::TransferToDevice`` (or ``...FromDevice``) and ends
with the matching ``...=>IssueEvent=>Done``, which names the chip
(``chip_id``) and the bytes (``size``).  A chip counts as busy while
it runs an operation or a copy to or from it is under way.  The
benchmark's own host spans (``bench.window``, ``bench.call``,
``bench.wait``) are on the host plane too, on the same clock, and so
are the TPU runtime's own events (``RUNTIME``): PJRT's call into the
program (``PJRT_LoadedExecutable_Execute``), each chip's launch on a
thread of its own (``TpuLoadedExecutable::ExecuteLaunch``), and each
chip's completion thread reading the chip's flag that the program has
ended (``ReadSyncFlag``) and then running the result's callbacks
(``CompleteCallbacks``).  Every number is taken inside the
``bench.window`` span.
"""

from __future__ import annotations

import bisect
import collections
import re
import statistics
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
BENCH_SPAN = "bench."
TRANSFERS = {"tpu::System::TransferToDevice": "h2d",
             "tpu::System::TransferFromDevice": "d2h"}
DONE = "=>IssueEvent=>Done"
#: the TPU runtime's host events that split a call's round trip
EXECUTE = "PJRT_LoadedExecutable_Execute"
LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"
SYNC = "ReadSyncFlag"
COMPLETE = "CompleteCallbacks"
RUNTIME = (EXECUTE, LAUNCH, SYNC, COMPLETE)


_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9-]*)\(")


def opcode(op: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event, whose name is the
    instruction's text (``%add.1 = f32[1,8]{1,0} add(...)``)."""
    m = _OPCODE.search(op.split(" = ", 1)[-1])
    return m.group(1) if m else op


def short(op: str) -> str:
    """An ``XLA Ops`` event's name up to its opcode: the result's name
    and shape, without the operands."""
    lhs, eq, rhs = op.partition(" = ")
    m = _OPCODE.search(rhs)
    return lhs + eq + rhs[:m.end(1)] if eq and m else op


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    """Device operations per chip and the benchmark's host spans, in
    nanoseconds on the profiler's clock."""

    def __init__(self, devices: dict[int, list[tuple[float, float, str]]],
                 spans: dict[str, list[tuple[float, float]]],
                 transfers: list[tuple[float, float, str, int]] = (),
                 runtime: dict[str, list[tuple[float, float]]] | None = None):
        #: chip -> its operations and its copies, as (start, end, name)
        self.devices = {k: list(v) for k, v in sorted(devices.items())}
        for s, e, direction, chip in transfers:
            self.devices.setdefault(chip, []).append((s, e, direction))
        for ops in self.devices.values():
            ops.sort()
        self.transfers = sorted(transfers)
        self.spans = {k: sorted(v) for k, v in spans.items()}
        #: the runtime's events by name (``RUNTIME``), as (start, end)
        self.runtime = {k: sorted(v) for k, v in (runtime or {}).items()}
        win = self.spans.get("bench.window", [])
        if len(win) != 1:
            raise ValueError(f"want one bench.window span, found {len(win)}")
        self.lo, self.hi = win[0]

    # -- the window and the device's busy time -----------------------------

    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(union_ns([(s, e) for s, e, _ in ops], self.lo, self.hi)
                   for ops in self.devices.values()) / len(self.devices) / 1e9

    def idle_share(self) -> float | None:
        if not self.devices:
            return None
        return 1.0 - self.busy_s() / self.window_s()

    def op_seconds(self, match) -> float:
        """Device seconds of the operations whose name ``match`` accepts,
        inside the window, averaged over the chips."""
        if not self.devices:
            return 0.0
        tot = sum(min(e, self.hi) - max(s, self.lo)
                  for ops in self.devices.values() for s, e, name in ops
                  if match(name) and e > self.lo and s < self.hi)
        return tot / len(self.devices) / 1e9

    # -- what the next reader sees ------------------------------------------

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most device time (seconds, averaged
        over the chips), and the device's idle time by the host span it
        fell in (seconds, averaged over the chips)."""
        by_op = collections.Counter()
        idle = collections.Counter()
        host = sorted((s, e, k) for k, v in self.spans.items()
                      if k != "bench.window" for s, e in v)
        ends = [e for _, e, _ in host]
        for ops in self.devices.values():
            for s, e, name in ops:
                if e > self.lo and s < self.hi:
                    by_op[short(name)] += min(e, self.hi) - max(s, self.lo)
            for gs, ge in gaps_ns([(s, e) for s, e, _ in ops],
                                  self.lo, self.hi):
                for label, part in _split(gs, ge, host, ends):
                    idle[label] += part
        k = max(len(self.devices), 1)
        return {"device_ops": [[n, v / k / 1e9]
                               for n, v in by_op.most_common(top)],
                "idle_gaps": [[n, v / k / 1e9]
                              for n, v in idle.most_common(top)]}

    # -- the runtime's share of each call's round trip -----------------------

    def runtime_calls(self) -> list[dict[str, list[tuple[float, float]]]]:
        """Per ``bench.call``, the runtime's events that start during it:
        from its start to the next call's start (the last call's, to the
        window's end)."""
        calls = [s for s, _ in self.spans.get("bench.call", [])]
        out = [{k: [] for k in RUNTIME} for _ in calls]
        for name in RUNTIME:
            for s, e in self.runtime.get(name, []):
                i = bisect.bisect_right(calls, s) - 1
                if i >= 0 and s < self.hi:
                    out[i][name].append((s, e))
        return out

    def runtime_launch_us(self) -> float | None:
        """Median over calls of the runtime's launch: from the start of
        the call's first ``EXECUTE`` to the end of its last ``LAUNCH``,
        on whichever chip's thread ends last.  None where no call holds
        both."""
        per_call = [max(e for _, e in c[LAUNCH]) - c[EXECUTE][0][0]
                    for c in self.runtime_calls() if c[EXECUTE] and c[LAUNCH]]
        return statistics.median(per_call) / 1e3 if per_call else None

    def runtime_complete_us(self) -> float | None:
        """Median over calls of the runtime's completion: from the start
        of the first ``SYNC`` that starts after the call's last
        ``LAUNCH`` has ended, to the end of the last ``COMPLETE`` that
        starts after it, on whichever chip's thread ends last.  None
        where no call holds all three."""
        per_call = []
        for c in self.runtime_calls():
            if not c[LAUNCH]:
                continue
            launched = max(e for _, e in c[LAUNCH])
            syncs = [s for s, _ in c[SYNC] if s >= launched]
            done = [e for s, e in c[COMPLETE] if s >= launched]
            if syncs and done:
                per_call.append(max(done) - syncs[0])
        return statistics.median(per_call) / 1e3 if per_call else None


def _split(gs: float, ge: float, host, ends) -> list[tuple[str, float]]:
    """Share the gap ``[gs, ge]`` out among the host spans (one after
    another, sorted, with their ``ends``) that overlap it; the rest is
    ``between_calls``."""
    out, covered = [], 0.0
    for j in range(bisect.bisect_right(ends, gs), len(host)):
        s, e, label = host[j]
        if s >= ge:
            break
        part = min(e, ge) - max(s, gs)
        if part > 0:
            out.append((label, part))
            covered += part
    if ge - gs - covered > 0:
        out.append(("between_calls", ge - gs - covered))
    return out


def _pair(starts, dones):
    """Match each copy's start with its completion, first in first out."""
    dones = sorted(dones)
    return [(s, max(e, s), chip) for s, (e, chip) in zip(sorted(starts), dones)]


def from_profile(pd) -> Trace:
    devices, spans = {}, collections.defaultdict(list)
    runtime = collections.defaultdict(list)
    starts = {d: [] for d in TRANSFERS.values()}
    dones = {d: [] for d in TRANSFERS.values()}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            chip = int(plane.name[len(DEVICE_PLANE):])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[chip] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(BENCH_SPAN):
                        spans[name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
                    elif name in RUNTIME:
                        runtime[name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
                    elif name in TRANSFERS:
                        starts[TRANSFERS[name]].append(e.start_ns)
                    elif name.endswith(DONE) and name[:-len(DONE)] in TRANSFERS:
                        chip = int(dict(e.stats).get("chip_id", 0))
                        dones[TRANSFERS[name[:-len(DONE)]]].append(
                            (e.start_ns + e.duration_ns, chip))
    transfers = [(s, e, d, chip) for d in TRANSFERS.values()
                 for s, e, chip in _pair(starts[d], dones[d])]
    return Trace(devices, dict(spans), transfers, dict(runtime))


def profile(trace_dir):
    """The profile that one profiler session wrote under ``trace_dir``,
    as ``jax.profiler.ProfileData``."""
    import jax

    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(str(found[-1]))
