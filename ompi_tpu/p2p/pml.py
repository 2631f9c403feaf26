"""Point-to-point messaging layer — the pml/ob1-equivalent.

TPU-native re-design of ``ompi/mca/pml/ob1`` (SURVEY.md §2.2: the
matching engine under MPI_Send/Recv, fragment callbacks
``mca_pml_ob1_recv_frag_callback_match`` [bin]) reduced to its semantic
core. In the single-controller model every rank lives in one address
space and all bulk data is resident on the fabric, so ob1's byte
machinery (BTL scheduling, eager/rendezvous, convertor fragmentation)
collapses; what remains — and is preserved faithfully — is **MPI
matching semantics**:

* posted-receive queue + unexpected-message queue per communicator
  (the two queues at the heart of ob1's matching);
* match on (source, tag) with ``ANY_SOURCE``/``ANY_TAG`` wildcards;
* the non-overtaking rule: messages from the same (source, comm) match
  posted receives in send order;
* ``Status`` carrying (source, tag, count); probe/iprobe.

Send is **buffered eager**: the payload is copied at send time (device
arrays: device-to-device put onto the receiver's device — the ICI
analog of the sm BTL's copy-in/copy-out), so the sender's buffer is
immediately reusable, matching MPI_Send's local-completion liberty.
"""

from __future__ import annotations

import collections
import threading
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np

from ompi_tpu.core.errors import MPIArgError, MPIRankError
from ompi_tpu.metrics import core as _metrics
from ompi_tpu.request import Request
from ompi_tpu.tool import spc
from ompi_tpu.trace import core as _trace
from ompi_tpu.trace import waitgraph as _waitgraph

ANY_SOURCE = -1
ANY_TAG = -1
PROC_NULL = -2


@dataclass
class Status:
    """MPI_Status: envelope of a completed/probed receive."""

    source: int
    tag: int
    count: int  # elements of the payload's dtype
    nbytes: int = 0  # payload bytes (what the C ABI's status carries)

    @classmethod
    def null(cls) -> "Status":
        return cls(PROC_NULL, ANY_TAG, 0, 0)


def _copy_payload(buf, dest_device=None):
    """Eager-copy the payload; device arrays hop to the receiver's
    device (ICI put), host arrays are copied."""
    if isinstance(buf, np.ndarray):
        return buf.copy()
    if isinstance(buf, jax.Array):
        if dest_device is not None:
            return jax.device_put(buf, dest_device)
        return jax.numpy.copy(buf)
    return np.asarray(buf).copy()


def _count_of(payload) -> int:
    try:
        return int(np.prod(np.shape(payload)))
    except Exception:
        return 0


def _nbytes_of(payload) -> int:
    try:
        return int(payload.nbytes)
    except AttributeError:
        try:
            return int(np.asarray(payload).nbytes)
        except Exception:
            return 0


@dataclass
class _Posted:
    source: int
    tag: int
    request: "RecvRequest"
    seq: int


@dataclass
class _Unexpected:
    source: int
    tag: int
    payload: Any
    seq: int


class RecvRequest(Request):
    """Pending receive; completed by the matching engine."""

    def __init__(self):
        super().__init__()
        self._event = threading.Event()
        self.status: Status | None = None
        self._payload: Any = None
        #: cross-process receives: (timeout_s, check, escalate) armed
        #: by the comm layer — see :meth:`arm_remote_guard`
        self._guard = None

    def _deliver(self, payload: Any, status: Status) -> None:
        self._payload = payload
        self.status = status
        self._event.set()

    def arm_remote_guard(self, timeout: float, check, escalate) -> None:
        """Make the blocking wait failure- and deadline-sensitive for a
        receive whose sender lives in another process: ``check()``
        raises once the watched peer is marked failed (ULFM in-band
        error instead of waiting out the deadline), ``escalate(t)``
        raises when the shared ``dcn_recv_timeout`` deadline expires —
        a remote receive must never hang.  Local receives stay
        unguarded: blocking on a not-yet-posted local send is plain
        MPI semantics, not a transport fault."""
        self._guard = (float(timeout), check, escalate)

    def _poll(self) -> bool:
        return self._event.is_set()

    def _block(self) -> None:
        if self._guard is None:
            self._event.wait()
            return
        from ompi_tpu.core.var import Deadline

        timeout, check, escalate = self._guard
        dl = Deadline(timeout)
        wtok = 0
        try:
            while not self._event.wait(dl.slice(0.25)):
                # hang diagnosis: one full slice without delivery is a
                # blocked wait — register lazily (first failed slice)
                if not wtok and _waitgraph._enabled:
                    wtok = _waitgraph.begin(
                        "p2p_recv",
                        peer=getattr(self, "wait_peer", None),
                        plane="host")
                check()
                if dl.expired():
                    escalate(timeout)
                    # escalate returning (not raising) means it chose to
                    # keep waiting — the ANY_SOURCE liveness guard with
                    # every member alive; re-arm so the wait does not
                    # degenerate into a 1 ms busy spin on an expired clock
                    dl = Deadline(timeout)
        finally:
            if wtok:
                _waitgraph.end(wtok)

    def _finalize(self) -> Any:
        return self._payload


class MatchingEngine:
    """Per-communicator matching state (≈ ob1's per-comm match tables).

    Matching walks the queues in arrival order, so the MPI
    non-overtaking guarantee holds: for a given (source, tag) the
    earliest-sent unexpected message (lowest seq) matches first, and
    the earliest-posted receive wins an incoming message.
    """

    def __init__(self, comm_size: int):
        self.comm_size = comm_size
        self._lock = threading.Lock()
        self._seq = 0
        # per destination rank
        self._posted: dict[int, list[_Posted]] = collections.defaultdict(list)
        self._unexpected: dict[int, list[_Unexpected]] = collections.defaultdict(list)

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _check_rank(self, r: int, wild_ok: bool = False) -> None:
        if r == PROC_NULL:
            return
        if wild_ok and r == ANY_SOURCE:
            return
        if not 0 <= r < self.comm_size:
            raise MPIRankError(f"rank {r} outside [0, {self.comm_size})")

    # -- send ----------------------------------------------------------

    def send(self, source: int, dest: int, payload: Any, tag: int,
             dest_device=None, _account: bool = True) -> None:
        """_account=False marks a relayed delivery (DCN frame already
        accounted on the SENDING process) — SPC counts stay sender-side."""
        self._check_rank(source)
        self._check_rank(dest)
        if dest == PROC_NULL:
            return
        if tag < 0:
            raise MPIArgError(f"send tag must be >= 0, got {tag}")
        if _account and spc.attached():
            spc.inc("send")
            spc.inc("send_bytes", spc.payload_nbytes(payload))
        if _account and _metrics._enabled:
            _metrics.observe_size("p2p_send", spc.payload_nbytes(payload))
        sp = _trace.span("p2p", "send", src=source, dst=dest, tag=tag) \
            if _trace._enabled else None
        try:
            data = _copy_payload(payload, dest_device)
            with self._lock:
                seq = self._next_seq()
                posted = self._posted[dest]
                for i, p in enumerate(posted):
                    if (p.source in (ANY_SOURCE, source)) and (p.tag in (ANY_TAG, tag)):
                        posted.pop(i)
                        p.request._deliver(
                            data,
                            Status(source, tag, _count_of(data), _nbytes_of(data)),
                        )
                        if sp is not None:
                            sp.args.update(matched=True, nbytes=_nbytes_of(data))
                        return
                self._unexpected[dest].append(_Unexpected(source, tag, data, seq))
            if sp is not None:
                sp.args.update(matched=False, nbytes=_nbytes_of(data))
        finally:
            if sp is not None:
                sp.end()

    # -- recv ----------------------------------------------------------

    def irecv(self, dest: int, source: int = ANY_SOURCE, tag: int = ANY_TAG) -> RecvRequest:
        self._check_rank(dest)
        self._check_rank(source, wild_ok=True)
        spc.inc("irecv")
        if _trace._enabled:
            _trace.instant("p2p", "irecv", dst=dest, src=source, tag=tag)
        req = RecvRequest()
        if source == PROC_NULL:
            req._deliver(None, Status.null())
            return req
        with self._lock:
            uq = self._unexpected[dest]
            best = None
            for i, m in enumerate(uq):
                if (source in (ANY_SOURCE, m.source)) and (tag in (ANY_TAG, m.tag)):
                    if best is None or m.seq < uq[best].seq:
                        best = i
            if best is not None:
                m = uq.pop(best)
                req._deliver(
                    m.payload,
                    Status(m.source, m.tag, _count_of(m.payload),
                           _nbytes_of(m.payload)),
                )
                return req
            self._posted[dest].append(_Posted(source, tag, req, self._next_seq()))
        return req

    # -- probe ---------------------------------------------------------

    def iprobe(self, dest: int, source: int = ANY_SOURCE, tag: int = ANY_TAG) -> Status | None:
        """Non-blocking probe: envelope of the first matching unexpected
        message, without consuming it.  PROC_NULL probes "match"
        immediately with the null status (MPI 3.8.2)."""
        self._check_rank(dest)
        self._check_rank(source, wild_ok=True)
        if source == PROC_NULL:
            return Status.null()
        with self._lock:
            best = None
            for m in self._unexpected[dest]:
                if (source in (ANY_SOURCE, m.source)) and (tag in (ANY_TAG, m.tag)):
                    if best is None or m.seq < best.seq:
                        best = m
            if best is None:
                return None
            return Status(best.source, best.tag, _count_of(best.payload),
                          _nbytes_of(best.payload))

    def pending_unexpected(self, dest: int) -> int:
        with self._lock:
            return len(self._unexpected[dest])

    def pending_posted(self, dest: int) -> int:
        with self._lock:
            return len(self._posted[dest])
