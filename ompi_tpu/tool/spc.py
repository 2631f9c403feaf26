"""SPC — software performance counters.

≈ ``ompi/runtime/ompi_spc.c`` (SURVEY.md §5(d): "cheap in-path counters
exposed via MPI_T pvars", present since 4.0).  The reference counts at
the MPI API layer (MPI_Allreduce increments ``OMPI_SPC_ALLREDUCE``);
here the api/comm entry points call :func:`inc` the same way.  Counters
cost one dict update when attached and one boolean check when not (the
reference's compile-time gate becomes a runtime flag — ``--mca
runtime_spc_attach all`` ≈ the ``mpi_spc_attach_all`` var).

Every counter surfaces as an MPI_T pvar through
:mod:`ompi_tpu.tool.mpit`.

Reset semantics follow the metrics core's grow-only pvar index rule
(:mod:`ompi_tpu.metrics.core`): counters zero IN PLACE — a key once
touched stays in :func:`snapshot` forever, so a tool diffing two
snapshots across a reset never sees a name vanish, and cached pvar
handles keep naming the same variable.  ``*_bytes`` increments also
route their payload size through the metrics core's shared log2
histogram buckets when metrics are enabled — one bucket convention
across SPC, the per-op histograms, and the Prometheus export.
"""

from __future__ import annotations

import threading

from ompi_tpu.metrics import core as _metrics

_lock = threading.Lock()
_counters: dict[str, int] = {}
_attached = False

#: non-collective counter names (the reference's OMPI_SPC_* set trimmed
#: to events this framework actually increments; collective counters are
#: one per coll-table slot, appended by :func:`known`)
_BASE_KNOWN = (
    "send", "send_bytes", "irecv",
    "put", "put_bytes", "get", "get_bytes", "accumulate",
    "file_write_bytes", "file_read_bytes",
    "arena_stage_in", "arena_stage_bytes", "arena_donations",
    "arena_pool_alloc", "recycle_hits", "recycle_misses",
)

_known_cache: tuple[str, ...] | None = None


def known() -> tuple[str, ...]:
    """Every counter name this build can increment — the MPI_T pvar
    namespace.  Collective names are the coll-table slots (allreduce,
    iallreduce, allreduce_init, …), incremented by CollTable.lookup."""
    global _known_cache
    if _known_cache is None:
        from ompi_tpu.coll.module import all_slots  # lazy: import cycle

        _known_cache = tuple(all_slots()) + _BASE_KNOWN
    return _known_cache


def payload_nbytes(p) -> int:
    """Byte size of a send/collective payload (shared accounting helper)."""
    nb = getattr(p, "nbytes", None)
    if nb is not None:
        return int(nb)
    try:
        import numpy as _np

        return int(_np.asarray(p).nbytes)
    except Exception:
        return 0


def attach(flag: bool = True) -> None:
    """Enable/disable counting (≈ mpi_spc_attach_all)."""
    global _attached
    _attached = flag


def attached() -> bool:
    return _attached


def inc(name: str, n: int = 1) -> None:
    """Hot-path increment: one flag check when detached."""
    if not _attached:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n
    if _metrics._enabled and name.endswith("_bytes"):
        _metrics.observe_size("spc_" + name[:-len("_bytes")], n)


# -- C-ABI fast-path merge ----------------------------------------------
# The shim's C collective fast path never crosses embedded Python, so
# its MPI_Allreduce/Bcast/... calls cannot tick inc() — they accrue in
# a C-side per-op array instead (shim.c g_fp_coll_spc) and merge here
# at READ time: zero hot-path cost, and the spc_* pvars keep ticking
# under stock C programs.  Outside a shim-hosted process the symbol
# probe fails once and the merge is a no-op.

_NATIVE_SLOTS = ("barrier", "bcast", "reduce", "allreduce", "allgather")
_native_fn = None
_native_probed = False
_native_base: dict[str, int] = {}


def _native_counts() -> dict[str, int]:
    global _native_fn, _native_probed
    if not _native_probed:
        _native_probed = True
        try:
            import ctypes

            lib = ctypes.CDLL(None)
            fn = lib.tpumpi_coll_spc
            fn.argtypes = [ctypes.c_longlong * len(_NATIVE_SLOTS)]
            fn.restype = None
            _native_fn = fn
        except (OSError, AttributeError, TypeError):
            _native_fn = None
    if _native_fn is None:
        return {}
    import ctypes

    buf = (ctypes.c_longlong * len(_NATIVE_SLOTS))()
    _native_fn(buf)
    return {n: int(buf[i]) for i, n in enumerate(_NATIVE_SLOTS)}


def get(name: str) -> int:
    nat = 0
    if name in _NATIVE_SLOTS:
        nc = _native_counts()
        if nc:
            nat = max(0, nc[name] - _native_base.get(name, 0))
    with _lock:
        return _counters.get(name, 0) + nat


def snapshot() -> dict[str, int]:
    with _lock:
        out = dict(_counters)
    nc = _native_counts()
    for n, v in nc.items():
        d = max(0, v - _native_base.get(n, 0))
        if d or n in out:
            out[n] = out.get(n, 0) + d
    return out


def reset() -> None:
    """Zero every counter IN PLACE — touched keys stay visible in
    :func:`snapshot` (the grow-only index rule; dropping keys made
    post-reset snapshot diffs silently lose names).  The monotone
    C-side counts are re-baselined (the C plane is never written)."""
    with _lock:
        for k in _counters:
            _counters[k] = 0
    for n, v in _native_counts().items():
        _native_base[n] = v


def reset_one(name: str) -> None:
    """Zero a single counter (MPI_T pvar_reset on one handle); the key
    stays registered — index/name stability across resets."""
    with _lock:
        if name in _counters:
            _counters[name] = 0
    if name in _NATIVE_SLOTS:
        nc = _native_counts()
        if nc:
            _native_base[name] = nc[name]


def clear() -> None:
    """Drop all counter STATE including keys (tests only — never a
    pvar-reset path)."""
    with _lock:
        _counters.clear()
