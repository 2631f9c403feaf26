"""Communicators — the MPI object model over mesh + coll stack.

≈ ``ompi/communicator/`` (``ompi_comm_*`` [bin]: create/dup/split, CID
allocation, per-comm coll table; SURVEY.md §2.1, §3.2-"coll selection").

Single-controller adaptation: one Python process drives every rank, so
a ``Comm`` is the whole communicator, not one rank's view.  Buffers are
**rank-major**: leading axis indexes the communicator rank.  Each comm
owns a sub-``CommMesh`` (its ranks' devices) and a coll table stacked
from the selected coll components (xla → fabric, basic → host/jagged),
rebuilt per communicator exactly like comm_select in the reference.

Buffer flavors: numpy in → numpy out (staged through the mesh — the
accelerator H2D/D2H path); jax array in → jax array out (stays on
fabric).  Datatype-typed byte buffers go through the ``*_ddt`` entry
points, which run the convertor (pack → fabric op on leaf dtype →
unpack), the analog of ob1's convertor staging in SURVEY.md §3.3.
"""

from __future__ import annotations

import threading
from typing import Any, Sequence

import jax
import numpy as np

from ompi_tpu.core import mca
from ompi_tpu.core.errors import (
    MPIArgError,
    MPICommError,
    MPIKeyvalError,
    MPIRankError,
    MPIRootError,
    MPITypeError,
)
from ompi_tpu.coll.module import CollTable, select_coll_modules
from ompi_tpu.ddt.convertor import pack as ddt_pack, unpack as ddt_unpack
from ompi_tpu.ft import ulfm
from ompi_tpu.ddt.datatype import Datatype, from_numpy_dtype
from ompi_tpu.mesh.mesh import CommMesh
from ompi_tpu.op.op import SUM, Op
from ompi_tpu.p2p.part import PersistentP2PMixin
from ompi_tpu.request import ArrayRequest, Request
from ompi_tpu.tool import spc
from ompi_tpu.trace import core as _trace
from .group import Group, UNDEFINED

#: (op, dtype) pairs whose arg-check already passed — the check is a
#: pure function of the pair, so one validation per signature suffices
#: (the reference's per-call arg checks are compiled C; ours must not
#: rebuild a Datatype per call — VERDICT r1 weak #1).
_OP_CHECK_OK: set[tuple] = set()

#: concrete runtime types known to be jax device arrays — a set lookup
#: on type() is ~6× cheaper than isinstance() against the jax.Array ABC
#: on the per-call hot path (SURVEY.md §3.3 zero-setup loop)
_JAX_ARRAY_TYPES: set[type] = set()

#: MPI_Comm_split color for "give me no communicator"
COLOR_UNDEFINED = UNDEFINED

_cid_next = 0
_cid_lock = threading.Lock()


def _next_cid() -> int:
    """CID allocation (≈ ompi_comm_nextcid; trivially collision-free in
    a single controller)."""
    global _cid_next
    with _cid_lock:
        c = _cid_next
        _cid_next += 1
        return c


def _peek_cid() -> int:
    """The next CID this process would hand out — the proposal each
    process contributes to the multi-process CID agreement."""
    with _cid_lock:
        return _cid_next


def _reserve_cid_block(floor: int, n: int) -> int:
    """Multi-process CID agreement commit (≈ ompi_comm_nextcid's
    MAX-allreduce): having agreed ``floor`` = max over processes of
    ``_peek_cid()``, every participant reserves the identical block
    ``[floor, floor + n)`` and jumps its local counter past it —
    re-syncing any divergence from process-local comm construction."""
    global _cid_next
    with _cid_lock:
        _cid_next = max(_cid_next, floor + n)
        return floor


class Comm(PersistentP2PMixin):
    """An intra-communicator."""

    def __init__(self, group: Group, mesh: CommMesh, name: str = ""):
        if group.size != mesh.size:
            raise MPICommError(
                f"group size {group.size} != mesh size {mesh.size}"
            )
        self.group = group
        self.mesh = mesh
        self.cid = _next_cid()
        self.name = name or f"comm#{self.cid}"
        self._coll: CollTable | None = None
        self._pml = None
        self._attrs: dict[int, Any] = {}
        self._freed = False
        #: ULFM fault-tolerance state; None until a failure/revoke event
        #: touches this comm (zero-cost fast path: one attribute test)
        self._ft = None
        #: fast-path dispatch cache: (slot, op, shape, dtype, …) →
        #: (mca context, store version, compiled callable, its
        #: recycling variant or None)
        self._fast: dict[tuple, tuple] = {}
        #: per-slot last-signature identity cache in FRONT of _fast:
        #: (op, root, shape, dtype, ctx, version, fn, recycling fn,
        #: signature).  Hits when the caller reuses the same buffer
        #: signature (training loops do), with pure `is` compares — no
        #: tuple hash on the hot loop.
        self._hot: dict[str, tuple] = {}
        #: the arena's spare pool of this comm: signature → its latest
        #: device-path result (see mesh/arena.py)
        self._spares: dict[tuple, tuple] = {}
        #: last sharding object accepted by _stage (identity fast path)
        self._ok_sharding = None

    # -- basics --------------------------------------------------------

    @property
    def size(self) -> int:
        return self.group.size

    def _check(self):
        if self._freed:
            raise MPICommError(f"{self.name} has been freed")

    @property
    def coll(self) -> CollTable:
        """Per-comm coll table, built on first use (≈ comm_select at
        comm construction; lazy keeps comm creation cheap)."""
        self._check()
        if self._coll is None:
            ctx = mca.default_context()
            self._coll = select_coll_modules(self, ctx.framework("coll"))
        return self._coll

    def set_name(self, name: str) -> None:
        self.name = name

    @property
    def pml(self):
        """Per-comm matching engine from the selected pml component
        (≈ ob1's per-comm match tables; one pml per job)."""
        self._check()
        if self._pml is None:
            ctx = mca.default_context()
            comp = ctx.framework("pml").select_one()
            self._pml = comp.make_engine(self.size, self.name)
        return self._pml

    # -- errhandlers (MPI_Comm_set_errhandler family) -------------------

    def set_errhandler(self, errhandler) -> None:
        """MPI_Comm_set_errhandler.  The Python surface always raises
        typed exceptions (≈ ERRORS_RETURN); ERRORS_ARE_FATAL makes the
        C ABI abort on error, and a create_errhandler callback fires
        before either action."""
        from ompi_tpu.core.errors import Errhandler

        if not isinstance(errhandler, Errhandler):
            raise MPIArgError(f"not an Errhandler: {errhandler!r}")
        self._errhandler = errhandler

    def get_errhandler(self):
        """MPI_Comm_get_errhandler (default: ERRORS_RETURN — the
        exception-raising Python surface)."""
        from ompi_tpu.core import errors as _err

        return getattr(self, "_errhandler", _err.ERRORS_RETURN)

    # -- attribute caching (MPI_Comm_set_attr family) -------------------

    def set_attr(self, keyval: int, value: Any) -> None:
        self._check()
        self._attrs[keyval] = value

    def get_attr(self, keyval: int) -> Any:
        self._check()
        if keyval not in self._attrs:
            raise MPIKeyvalError(f"no attribute {keyval}")
        return self._attrs[keyval]

    def delete_attr(self, keyval: int) -> None:
        self._check()
        self._attrs.pop(keyval, None)

    # -- construction (dup/split/create) --------------------------------

    def _inherit(self, c: "Comm") -> "Comm":
        """Derived-comm property propagation (MPI-4 §9.5: errhandler is
        inherited by dup/create/split)."""
        if hasattr(self, "_errhandler"):
            c._errhandler = self._errhandler
        return c

    def dup(self, name: str = "") -> "Comm":
        self._check()
        return self._inherit(
            Comm(Group(self.group.ranks), self.mesh, name or f"{self.name}.dup")
        )

    def create_group(self, group: Group, name: str = "") -> "Comm | None":
        """MPI_Comm_create_group: new comm over a subset of this comm's
        ranks (group ranks are THIS comm's ranks)."""
        self._check()
        for r in group.ranks:
            if not 0 <= r < self.size:
                raise MPIRankError(f"rank {r} outside {self.name}")
        if group.size == 0:
            return None
        sub = self.mesh.submesh(group.ranks)
        world_ranks = [self.group.ranks[r] for r in group.ranks]
        return self._inherit(Comm(Group(world_ranks), sub, name))

    def split(self, colors: Sequence[int], keys: Sequence[int] | None = None) -> list["Comm | None"]:
        """MPI_Comm_split, whole-communicator view: ``colors[r]`` /
        ``keys[r]`` are rank r's arguments; returns per-rank comms
        (ranks sharing a color share the object; COLOR_UNDEFINED → None).
        Rank order within a color: (key, old rank), per the standard."""
        self._check()
        if len(colors) != self.size:
            raise MPIArgError("colors length != comm size")
        if keys is None:
            keys = [0] * self.size
        if len(keys) != self.size:
            raise MPIArgError("keys length != comm size")
        by_color: dict[int, list[int]] = {}
        for r, c in enumerate(colors):
            if c == COLOR_UNDEFINED:
                continue
            if c < 0:
                raise MPIArgError(f"negative color {c}")
            by_color.setdefault(c, []).append(r)
        out: list[Comm | None] = [None] * self.size
        for c, members in sorted(by_color.items()):
            members.sort(key=lambda r: (keys[r], r))
            comm = self.create_group(Group(members), name=f"{self.name}.split({c})")
            for r in members:
                out[r] = comm
        return out

    def _shrink_to(self, live: Sequence[int], name: str = "") -> "Comm":
        """ULFM shrink substrate: a fresh communicator over the live rank
        subset, renumbered contiguously, mesh shrunk to their devices
        (SURVEY.md §5: "slice-failure → shrink mesh → re-form").  Unlike
        create_group this works on revoked comms — shrink IS the
        recovery path — so no FT guard here."""
        self._check()
        sub = self.mesh.submesh(list(live))
        world_ranks = [self.group.ranks[r] for r in live]
        return Comm(Group(world_ranks), sub, name or f"{self.name}.shrunk")

    def revoke(self) -> None:
        """MPIX_Comm_revoke."""
        ulfm.revoke(self)

    def shrink(self, name: str = "") -> "Comm":
        """MPIX_Comm_shrink."""
        return ulfm.shrink(self, name)

    def agree(self, flags: int, contributions=None) -> int:
        """MPIX_Comm_agree."""
        return ulfm.agree(self, flags, contributions)

    def get_failed(self) -> list[int]:
        """MPIX_Comm_get_failed."""
        return ulfm.get_failed(self)

    def ack_failed(self) -> int:
        """MPIX_Comm_ack_failed."""
        return ulfm.ack_failed(self)

    def is_revoked(self) -> bool:
        """MPIX_Comm_is_revoked."""
        return ulfm.is_revoked(self)

    def split_type_shared(self) -> "Comm":
        """MPI_Comm_split_type(MPI_COMM_TYPE_SHARED): single-host/
        single-slice → everything is one shared domain."""
        return self.dup(name=f"{self.name}.shared")

    # -- one-sided windows (MPI_Win_* constructors; ≈ osc selection at
    # window creation, SURVEY.md §3.5) --------------------------------

    def _osc(self):
        return mca.default_context().framework("osc").select_one()

    def win_create(self, bases, name: str = ""):
        """MPI_Win_create: expose per-rank 1-D buffers for RMA."""
        self._check()
        return self._osc().win_create(self, bases, name=name)

    def win_allocate(self, size: int, dtype=np.float32, name: str = ""):
        self._check()
        return self._osc().win_allocate(self, size, dtype, name=name)

    def win_allocate_shared(self, size: int, dtype=np.float32, name: str = ""):
        self._check()
        return self._osc().win_allocate_shared(self, size, dtype, name=name)

    def win_create_dynamic(self, dtype=np.float32, name: str = ""):
        self._check()
        return self._osc().win_create_dynamic(self, dtype, name=name)

    # -- MPI-IO (MPI_File_open; ≈ io framework selection) --------------

    def file_open(self, path: str, amode: int, hints: dict | None = None):
        """MPI_File_open: collective open through the selected io
        component (io/ompio).  ``hints`` = MPI_Info key/values
        (striping_factor/striping_unit recognized)."""
        self._check()
        comp = mca.default_context().framework("io").select_one()
        return comp.file_open(self, path, amode, hints=hints)

    def free(self) -> None:
        self._check()
        if self._coll is not None:
            for m in self._coll.modules:
                m.disable()
        self._coll = None
        self._fast.clear()
        self._hot.clear()  # freed comms must not serve the hot path
        self.mesh.arena.drop_spares(self._spares)
        self._freed = True

    # -- buffer staging -------------------------------------------------

    def _stage(self, x, depth_expected: int):
        """Normalize a rank-major input; returns (device_array, was_host)."""
        is_dev = type(x) in _JAX_ARRAY_TYPES
        if not is_dev and isinstance(x, jax.Array) \
                and not isinstance(x, np.ndarray):
            _JAX_ARRAY_TYPES.add(type(x))  # learn the concrete type once
            is_dev = True
        if is_dev:
            # An array committed to devices outside this comm's mesh
            # (e.g. a gather result living on root) must be resharded or
            # jit rejects it; mesh-resident arrays pass through untouched.
            # jax interns sharding objects per (mesh, spec): an identity
            # hit on the last-accepted sharding skips the set compare
            # on the hot loop
            sh = x.sharding
            if sh is not self._ok_sharding:
                if sh.device_set != self.mesh.device_set:
                    x = jax.device_put(x, self.mesh.rank_sharding())
                else:
                    self._ok_sharding = sh
            return x, False
        arr = np.asarray(x)
        if arr.ndim < depth_expected or arr.shape[0] != self.size:
            raise MPIArgError(
                f"rank-major buffer must have shape ({self.size}, ...); got {arr.shape}"
            )
        return self.mesh.stage_in(arr), True

    def _unstage(self, out, was_host: bool):
        return self.mesh.stage_out(out) if was_host else out

    def _check_root(self, root: int) -> None:
        if not 0 <= root < self.size:
            raise MPIRootError(f"root {root} not in [0, {self.size})")

    def _check_op(self, op: Op, x) -> None:
        """Arg-check layer (≈ ompi/mpi/c/<coll>.c): reject op × dtype
        combinations the standard forbids BEFORE they reach XLA tracing.
        One Datatype construction per (op, dtype) pair, ever."""
        if not isinstance(op, Op):
            raise MPIArgError(f"op must be an ompi_tpu Op, got {type(op)}")
        dtype = getattr(x, "dtype", None)
        if dtype is None or (op, dtype) in _OP_CHECK_OK:
            return
        op.check(from_numpy_dtype(dtype))
        if len(_OP_CHECK_OK) > 4096:  # backstop vs unbounded user-op churn
            _OP_CHECK_OK.clear()
        _OP_CHECK_OK.add((op, dtype))

    # -- collectives (ndarray API) --------------------------------------
    # Each entry point: arg-check (≈ ompi/mpi/c/<coll>.c) then dispatch
    # through the comm's coll table (≈ comm->c_coll->coll_<op>).
    # Dispatch goes through a per-comm fast path: the winning module's
    # resolve() returns the compiled array→array program ONCE per call
    # signature; subsequent calls are one dict hit + the XLA dispatch —
    # the zero-per-call-setup hot loop of SURVEY.md §3.3 (VERDICT r1 #1).

    def _fast_fn(self, slot: str, base: str, key: tuple, args: tuple,
                 donate: bool = False, sp=None, recycle: bool = False):
        """Cached-or-resolved compiled programs for this call signature,
        as the cache entry ``(ctx, store version, program, recycling
        variant)``, or None when the winning module exposes no resolver
        (host/monitoring modules) — then the caller takes the table path.

        ``recycle``: a device-buffer call — resolve, in the same
        resolution, the variant that writes into a donated dropped
        result (None where the module gives none).  Every caller that
        shares a key with ``_coll_call`` passes it, so the entry always
        holds the variant there.

        ``donate``: the input is a framework-staged buffer this call
        owns — resolve the arena (donating) program variant if the
        accelerator component allows it.  The donate decision is read
        at RESOLUTION time only and baked into the cached callable
        (key carries the flag; store-version invalidation picks up
        --mca accelerator_tpu_donate_staged changes).

        ``sp``: the call's open api span, when tracing is on — a miss
        records its resolution as the child span ``coll.resolve``."""
        ctx = mca._default
        try:
            ent = self._fast[key]
            if ent[0] is ctx and ent[1] == ctx.store.version:
                if spc._attached:  # inlined flag test: this IS the hot loop
                    spc.inc(slot)
                return ent
        except KeyError:
            pass
        if ctx is None:
            return None
        resolve = getattr(self.coll.owners.get(slot), "resolve", None)
        if resolve is None:
            return None
        ver = ctx.store.version
        if donate:
            donate = bool(ctx.store.get("accelerator_tpu_donate_staged", True))
        if sp is None:
            fn, rfn = self._resolve(resolve, base, args, donate, recycle)
        else:
            with sp.child("coll", "resolve"):
                fn, rfn = self._resolve(resolve, base, args, donate, recycle)
        if fn is None:
            return None
        if len(self._fast) > 4096:  # user-op churn backstop
            self._fast.clear()
        ent = self._fast[key] = (ctx, ver, fn, rfn)
        spc.inc(slot)
        return ent

    @staticmethod
    def _resolve(resolve, base: str, args: tuple, donate: bool,
                 recycle: bool):
        fn = resolve(base, *args, donate=donate)
        if fn is None or not recycle:
            return fn, None
        return fn, resolve(base, *args, recycle=True)

    def _recycled(self, fn, rfn, sig: tuple, x, sp=None):
        """Run a blocking device-buffer call through the arena's spare
        pool (``HbmArena.run_recycled``): into the signature's dropped
        previous result where it qualifies, else a fresh allocation.
        ``sp``: the open api span, which gets the arg ``recycled``."""
        out, recycled = self.mesh.arena.run_recycled(
            self._spares, sig, fn, rfn, x)
        if sp is not None:
            sp.args["recycled"] = int(recycled)
        return out

    def _ft_guard(self) -> None:
        """The ULFM collective guard. Exactly three call sites —
        _dispatch, _dispatch_i (which bypass the table on their compiled
        fast path) and _lookup (every table-path entry) — so every
        collective entry is guarded structurally, never per-call-site."""
        if self._ft is not None:
            ulfm.check(self, collective=True)

    def _lookup(self, slot: str):
        """FT-guarded coll-table lookup: the choke point for every
        collective entry that does not go through _dispatch/_dispatch_i."""
        self._ft_guard()
        fn = self.coll.lookup(slot)
        if _trace._enabled:
            return _trace.wrap_call("api", slot, fn, comm=self.name)
        return fn

    def _api_span(self, slot: str, x):
        """Open the api-layer span of one collective call (tracing on)."""
        return _trace.span("api", slot, comm=self.name,
                           seq=_trace.next_seq(self.name, slot),
                           nbytes=spc.payload_nbytes(x))

    def _dispatch(self, slot: str, key: tuple, args: tuple, host: bool,
                  sp=None, recycle: bool = False):
        """Run one blocking collective through the compiled fast path,
        else the coll table.  ``sp``: a traced caller's open api span.
        ``recycle``: a device-buffer call of ``_coll_call`` — its
        result may go into the signature's dropped previous one."""
        self._ft_guard()
        if _trace._enabled:
            return self._dispatch_traced(slot, key, args, host, sp, recycle)
        # host inputs were staged into a buffer this call owns → the
        # arena's donating program variant may consume it (key carries
        # the flag so host/device callers never share a cache entry)
        ent = self._fast_fn(slot, slot, key + (host,), args, donate=host,
                            recycle=recycle)
        if ent is None:
            out = self.coll.lookup(slot)(*args)
        elif recycle and ent[3] is not None:
            out = self._recycled(ent[2], ent[3], key, args[0])
        else:
            out = ent[2](args[0])
        return self.mesh.stage_out(out) if host else out

    def _dispatch_traced(self, slot: str, key: tuple, args: tuple,
                         host: bool, sp, recycle: bool = False):
        """_dispatch with tracing on, inside the caller's api span or
        one of its own; ``coll.launch`` covers the compiled program's
        call."""
        own = sp is None
        if own:
            sp = self._api_span(slot, args[0])
        try:
            ent = self._fast_fn(slot, slot, key + (host,), args,
                                donate=host, sp=sp, recycle=recycle)
            if ent is None:
                out = self.coll.lookup(slot)(*args)
            else:
                with sp.child("coll", "launch"):
                    if recycle and ent[3] is not None:
                        out = self._recycled(ent[2], ent[3], key, args[0],
                                             sp)
                    else:
                        out = ent[2](args[0])
            return self.mesh.stage_out(out) if host else out
        finally:
            if own:
                sp.end()

    def _dispatch_i(self, slot: str, base: str, key: tuple, args: tuple,
                    host: bool) -> Request:
        """Non-blocking twin: the cached program is the SAME compiled
        callable as the blocking slot (shared key), wrapped in an
        ArrayRequest (async XLA dispatch ↔ libnbc schedule)."""
        self._ft_guard()
        # its results never enter the spare pool; ``recycle`` only keeps
        # the entry it shares with the blocking slot complete
        if _trace._enabled:
            with self._api_span(slot, args[0]) as sp:
                ent = self._fast_fn(slot, base, key + (host,), args,
                                    donate=host, sp=sp, recycle=not host)
                if ent is None:
                    req = self.coll.lookup(slot)(*args)
                else:
                    with sp.child("coll", "launch"):
                        req = ArrayRequest(ent[2](args[0]))
            return _wrap_unstage(req, self, host)
        ent = self._fast_fn(slot, base, key + (host,), args, donate=host,
                            recycle=not host)
        req = (ArrayRequest(ent[2](args[0])) if ent is not None
               else self.coll.lookup(slot)(*args))
        return _wrap_unstage(req, self, host)

    def _coll_call(self, slot: str, x, depth: int, op: Op | None = None,
              root: int | None = None):
        """Common path for the five hot collectives: a per-slot
        last-signature cache in FRONT of the keyed _fast cache.  On a
        hot hit (same op identity / root / shape / dtype as the last
        call on a mesh-resident buffer) the compiled callable is
        returned without tuple hashing or arg checks — those are pure
        functions of the signature and already passed once
        (SURVEY.md §3.3 zero-setup hot loop)."""
        if _trace._enabled:
            return self._coll_call_traced(slot, x, depth, op, root)
        if (
            self._ft is None
            and type(x) in _JAX_ARRAY_TYPES
            and x.sharding is self._ok_sharding
        ):
            c = self._hot.get(slot)
            if (
                c is not None
                and c[0] is op and c[1] == root
                and c[2] == x.shape and c[3] == x.dtype
                and c[4] is mca._default and c[5] == c[4].store.version
            ):
                if spc._attached:
                    spc.inc(slot)
                if c[7] is None:
                    return c[6](x)
                return self.mesh.arena.run_recycled(
                    self._spares, c[8], c[6], c[7], x)[0]
        return self._coll_miss(slot, x, depth, op, root)

    def _coll_call_traced(self, slot: str, x, depth: int, op: Op | None,
                          root: int | None):
        """_coll_call with tracing on: one api span covers the whole
        call, from the entry on; its ``hot`` arg says whether the
        last-signature cache served it (the same test as
        _coll_call's), ``recycled`` whether the result went into a
        dropped previous one, and ``coll.launch`` covers the compiled
        program's call."""
        with self._api_span(slot, x) as sp:
            if (
                self._ft is None
                and type(x) in _JAX_ARRAY_TYPES
                and x.sharding is self._ok_sharding
            ):
                c = self._hot.get(slot)
                if (
                    c is not None
                    and c[0] is op and c[1] == root
                    and c[2] == x.shape and c[3] == x.dtype
                    and c[4] is mca._default and c[5] == c[4].store.version
                ):
                    if spc._attached:
                        spc.inc(slot)
                    sp.args["hot"] = 1
                    with sp.child("coll", "launch"):
                        if c[7] is None:
                            sp.args["recycled"] = 0
                            return c[6](x)
                        return self._recycled(c[6], c[7], c[8], x, sp)
            sp.args["hot"] = 0
            sp.args["recycled"] = 0
            return self._coll_miss(slot, x, depth, op, root, sp)

    def _coll_miss(self, slot: str, x, depth: int, op: Op | None,
                   root: int | None, sp=None):
        """_coll_call past the last-signature cache: check, stage and
        dispatch, then remember the signature.  The key is built ONCE
        here, so _dispatch and the hot store can never diverge."""
        if op is not None:
            self._check_op(op, x)
        if root is not None:
            self._check_root(root)
        xd, host = self._stage(x, depth)
        key = (slot, op, root, xd.shape, xd.dtype)
        args = (xd,) + ((op,) if op is not None else ()) \
            + ((root,) if root is not None else ())
        out = self._dispatch(slot, key, args, host, sp, recycle=not host)
        if not host:
            ent = self._fast.get(key + (False,))
            if ent is not None:
                self._hot[slot] = (op, root, xd.shape, xd.dtype,
                                   ent[0], ent[1], ent[2], ent[3], key)
        return out

    def allreduce(self, x, op: Op = SUM):
        return self._coll_call("allreduce", x, 1, op=op)

    def iallreduce(self, x, op: Op = SUM) -> Request:
        self._check_op(op, x)
        xd, host = self._stage(x, 1)
        return self._dispatch_i(
            "iallreduce", "allreduce",
            ("allreduce", op, None, xd.shape, xd.dtype), (xd, op), host,
        )

    def _sched_fn(self, base: str, args: tuple, op: Op | None = None,
                  root: int | None = None):
        """Persistent-collective plan from the PROCESS-WIDE compiled-
        schedule cache (:mod:`ompi_tpu.coll.sched`): keyed by comm
        SHAPE (mesh devices), not comm identity, so a fresh communicator
        of the same shape — a dup, or the next job in a resident tpud
        worker — replays the already-compiled program instead of
        re-resolving and re-compiling it.  None when the winning module
        exposes no resolver (host/monitoring modules) — the caller
        takes the table path."""
        ctx = mca._default
        if ctx is None:
            return None
        owner = self.coll.owners.get(base)
        resolve = getattr(owner, "resolve", None)
        if resolve is None:
            return None
        from ompi_tpu.coll import sched as _sched

        xd = args[0]
        mesh_key = tuple(
            (str(getattr(d, "platform", "")), int(getattr(d, "id", 0)))
            for d in self.mesh.devices)
        key = ("pers", base, mesh_key, op, root, xd.shape, str(xd.dtype),
               ctx.store.version)
        # donate stays False: a persistent request re-dispatches on the
        # SAME staged buffer every start — donation would consume it
        return _sched.lookup(key, lambda: resolve(base, *args))

    def _pers_coll(self, base: str, args: tuple, op: Op | None = None,
                   root: int | None = None) -> Request | None:
        # the structural ULFM guard: the cached-plan path bypasses
        # _lookup, so it must guard here like _dispatch/_dispatch_i do
        self._ft_guard()
        fn = self._sched_fn(base, args, op=op, root=root)
        if fn is None:
            return None
        from ompi_tpu.request import ArrayRequest, PersistentRequest

        xd = args[0]
        return PersistentRequest(lambda: ArrayRequest(fn(xd)))

    def allreduce_init(self, x, op: Op = SUM) -> Request:
        self._check_op(op, x)
        xd, _ = self._stage(x, 1)
        req = self._pers_coll("allreduce", (xd, op), op=op)
        return req if req is not None \
            else self._lookup("allreduce_init")(xd, op)

    def bcast_init(self, x, root: int = 0) -> Request:
        self._check_root(root)
        xd, _ = self._stage(x, 1)
        req = self._pers_coll("bcast", (xd, root), root=root)
        return req if req is not None \
            else self._lookup("bcast_init")(xd, root)

    def allgather_init(self, x) -> Request:
        xd, _ = self._stage(x, 1)
        req = self._pers_coll("allgather", (xd,))
        return req if req is not None \
            else self._lookup("allgather_init")(xd)

    def bcast(self, x, root: int = 0):
        return self._coll_call("bcast", x, 1, root=root)

    def ibcast(self, x, root: int = 0) -> Request:
        self._check_root(root)
        xd, host = self._stage(x, 1)
        return self._dispatch_i(
            "ibcast", "bcast", ("bcast", None, root, xd.shape, xd.dtype),
            (xd, root), host,
        )

    def reduce(self, x, op: Op = SUM, root: int = 0):
        """Returns the reduced array (the standard says only root's
        recvbuf is defined; single-controller returns it once)."""
        self._check_op(op, x)
        self._check_root(root)
        xd, host = self._stage(x, 1)
        out = self._dispatch(
            "reduce", ("reduce", op, xd.shape, xd.dtype, root),
            (xd, op, root), host,
        )
        return out[root] if hasattr(out, "__getitem__") else out

    def allgather(self, x):
        return self._coll_call("allgather", x, 1)

    def iallgather(self, x) -> Request:
        xd, host = self._stage(x, 1)
        return self._dispatch_i(
            "iallgather", "allgather",
            ("allgather", None, None, xd.shape, xd.dtype), (xd,), host,
        )

    def gather(self, x, root: int = 0):
        """Returns root's recvbuf: (n, *s) gathered blocks (resident on
        root's device on the fabric path)."""
        self._check_root(root)
        xd, host = self._stage(x, 1)
        return self._dispatch(
            "gather", ("gather", xd.shape, xd.dtype, root), (xd, root), host
        )

    def scatter(self, x, root: int = 0):
        """x: root's sendbuf (n, *s); returns (n, *s) rank-major (row r
        is rank r's recvbuf)."""
        self._check_root(root)
        xd, host = self._stage(x, 1)
        return self._dispatch(
            "scatter", ("scatter", xd.shape, xd.dtype, root), (xd, root), host
        )

    def reduce_scatter_block(self, x, op: Op = SUM):
        return self._coll_call("reduce_scatter_block", x, 2, op=op)

    def reduce_scatter(self, x, op: Op = SUM, counts: Sequence[int] | None = None):
        """MPI_Reduce_scatter. ``counts`` per-rank receive counts:
        jagged → host path (list results); equal counts c → each rank's
        (n*c, *tail) sendbuf is reshaped to blocks and reduced on the
        fabric, returning (n, c, *tail); counts=None → x is already in
        block form (n, n, *s)."""
        self._check_op(op, x)
        if counts is not None:
            if len(counts) != self.size:
                raise MPIArgError("reduce_scatter counts length != comm size")
            if len(set(counts)) > 1:
                # jagged → host path via the table (lists)
                return self._lookup("reduce_scatter")(np.asarray(x), op, counts)
            c = counts[0]
            arr = np.asarray(x) if not isinstance(x, jax.Array) else x
            if arr.shape[1] != self.size * c:
                raise MPIArgError(
                    f"reduce_scatter sendbuf dim1 {arr.shape[1]} != n*count "
                    f"{self.size * c}"
                )
            blocks = arr.reshape((self.size, self.size, c) + arr.shape[2:])
            xd, host = self._stage(blocks, 2)
            out = self._lookup("reduce_scatter_block")(xd, op)
            return self._unstage(out, host)
        xd, host = self._stage(x, 2)
        return self._unstage(self._lookup("reduce_scatter")(xd, op, None), host)

    def alltoall(self, x):
        return self._coll_call("alltoall", x, 2)

    def ialltoall(self, x) -> Request:
        xd, host = self._stage(x, 2)
        return self._dispatch_i(
            "ialltoall", "alltoall",
            ("alltoall", None, None, xd.shape, xd.dtype), (xd,), host,
        )

    def scan(self, x, op: Op = SUM):
        self._check_op(op, x)
        xd, host = self._stage(x, 1)
        return self._dispatch(
            "scan", ("scan", op, xd.shape, xd.dtype), (xd, op), host
        )

    def exscan(self, x, op: Op = SUM):
        self._check_op(op, x)
        xd, host = self._stage(x, 1)
        return self._dispatch(
            "exscan", ("exscan", op, xd.shape, xd.dtype), (xd, op), host
        )

    def barrier(self) -> None:
        self._lookup("barrier")()

    def ibarrier(self) -> Request:
        return self._lookup("ibarrier")()

    # jagged variants (host path)
    def allgatherv(self, blocks: Sequence[np.ndarray]):
        if len(blocks) != self.size:
            raise MPIArgError("allgatherv needs one block per rank")
        return self._lookup("allgatherv")(blocks)

    def alltoallv(self, matrix: Sequence[Sequence[np.ndarray]]):
        if len(matrix) != self.size:
            raise MPIArgError("alltoallv needs n rows")
        return self._lookup("alltoallv")(matrix)

    def gatherv(self, blocks: Sequence[np.ndarray], root: int = 0):
        self._check_root(root)
        return self._lookup("gatherv")(blocks, root)

    def scatterv(self, blocks: Sequence[np.ndarray], root: int = 0):
        self._check_root(root)
        return self._lookup("scatterv")(blocks, root)

    # -- point-to-point (pml) -------------------------------------------

    def send(self, buf, source: int, dest: int, tag: int = 0) -> None:
        """MPI_Send from rank ``source`` to ``dest`` (single-controller
        form names both endpoints). Eager-buffered: returns immediately,
        sender's buffer reusable."""
        if self._ft is not None:
            ulfm.check(self, peer=dest)
        dest_dev = (
            self.mesh.devices[dest]
            if isinstance(buf, jax.Array) and 0 <= dest < self.size
            else None
        )
        self.pml.send(source, dest, buf, tag, dest_dev)

    def isend(self, buf, source: int, dest: int, tag: int = 0) -> Request:
        from ompi_tpu.request import CompletedRequest

        self.send(buf, source, dest, tag)
        return CompletedRequest()  # eager send completes locally

    def irecv(self, dest: int, source: int | None = None, tag: int | None = None) -> Request:
        from ompi_tpu.p2p.pml import ANY_SOURCE, ANY_TAG

        if self._ft is not None:
            ulfm.check(self, peer=source, any_source=source is None)
        return self.pml.irecv(
            dest,
            ANY_SOURCE if source is None else source,
            ANY_TAG if tag is None else tag,
        )

    def recv(self, dest: int, source: int | None = None, tag: int | None = None):
        """MPI_Recv at rank ``dest``; returns (payload, Status)."""
        req = self.irecv(dest, source, tag)
        payload = req.wait()
        return payload, req.status

    def sendrecv(
        self, sendbuf, source: int, dest: int, recv_source: int,
        sendtag: int = 0, recvtag: int | None = None,
    ):
        """MPI_Sendrecv at rank ``source``: send to ``dest``, receive
        from ``recv_source``. Deadlock-free by eager buffering."""
        self.send(sendbuf, source, dest, sendtag)
        return self.recv(source, recv_source, recvtag)

    def probe(self, dest: int, source: int | None = None, tag: int | None = None):
        """MPI_Probe (blocking): wait for a matching envelope."""
        from ompi_tpu.request import _poll_backoff

        sleep = 0.0
        while True:
            st = self.iprobe(dest, source, tag)
            if st is not None:
                return st
            sleep = _poll_backoff(sleep)

    def iprobe(self, dest: int, source: int | None = None, tag: int | None = None):
        from ompi_tpu.p2p.pml import ANY_SOURCE, ANY_TAG

        if self._ft is not None:
            # guard here (not just irecv) so blocking probe raises
            # instead of spinning forever on a revoked comm / dead peer
            ulfm.check(self, peer=source, any_source=source is None)
        return self.pml.iprobe(
            dest,
            ANY_SOURCE if source is None else source,
            ANY_TAG if tag is None else tag,
        )

    # -- datatype (convertor) entry points ------------------------------

    def allreduce_ddt(
        self,
        sendbufs: Sequence[Any],
        count: int,
        datatype: Datatype,
        op: Op = SUM,
        recvbufs: Sequence[Any] | None = None,
    ):
        """MPI_Allreduce over typed byte buffers: per-rank buffers are
        packed via the convertor (derived datatypes → gather), reduced
        on the fabric in leaf dtype, and unpacked into ``recvbufs``
        (or fresh packed arrays are returned).

        ≈ SURVEY.md §3.3: convertor_pack → transport → op → unpack, with
        the transport collapsed into the fabric collective."""
        op.check(datatype)
        if len(sendbufs) != self.size:
            raise MPIArgError("one send buffer per rank required")
        if datatype.uniform_leaf is None:
            raise MPITypeError("reductions need a uniform-leaf datatype")
        leaf = datatype.uniform_leaf
        packed = [
            ddt_pack(b, datatype, count).view(leaf) for b in sendbufs
        ]
        stacked = np.stack(packed)  # (n, count*leaves)
        red = self.allreduce(stacked, op)
        red = np.asarray(red)
        if recvbufs is not None:
            if len(recvbufs) != self.size:
                raise MPIArgError("one recv buffer per rank required")
            for r in range(self.size):
                ddt_unpack(
                    recvbufs[r], datatype, count,
                    np.ascontiguousarray(red[r]).view(np.uint8),
                )
            return recvbufs
        return red

    def bcast_ddt(self, buf, count: int, datatype: Datatype, root: int = 0):
        """Typed bcast: packs root's buffer, broadcasts, returns per-rank
        unpacked byte buffers."""
        self._check_root(root)
        packed = ddt_pack(buf, datatype, count)
        stacked = np.stack([packed] * self.size)
        out = np.asarray(self.bcast(stacked, root))
        bufs = []
        for r in range(self.size):
            dst = np.zeros(datatype.lb + datatype.span(count), np.uint8)
            ddt_unpack(dst, datatype, count, np.ascontiguousarray(out[r]))
            bufs.append(dst)
        return bufs

    def __repr__(self) -> str:  # pragma: no cover
        return f"<Comm {self.name} size={self.size} cid={self.cid}>"


def _wrap_unstage(req: Request, comm: Comm, was_host: bool) -> Request:
    """Chain a D2H unstage onto a device request for host callers."""
    if not was_host:
        return req

    class _Unstage(Request):
        def _poll(self):
            return req.test()

        def _block(self):
            req.wait()

        def _finalize(self):
            return comm.mesh.stage_out(req.wait())

    return _Unstage()
