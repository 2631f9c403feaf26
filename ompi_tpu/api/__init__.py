"""MPI API layer (≈ ompi/mpi/c + ompi/runtime, SURVEY.md §3.2).

``init()`` ≈ MPI_Init: builds the MCA context from ``--mca``-style
params, brings up the persistent world mesh, and constructs COMM_WORLD
(+ COMM_SELF). ``finalize()`` ≈ MPI_Finalize.
"""

from __future__ import annotations

import jax

from ompi_tpu.core import mca
from ompi_tpu.core.errors import MPICommError
from .comm import COLOR_UNDEFINED, Comm
from .group import Group, UNDEFINED  # noqa: F401
from .info import INFO_NULL, Info, info_env  # noqa: F401
from .intercomm import Intercomm, create_intercomm  # noqa: F401
from .spawn import get_parent, spawn  # noqa: F401

_world: Comm | None = None
_self_comm: Comm | None = None
_initialized = False
#: serve plane (tpud): saved resident worlds while a job world is
#: pushed — ``init()`` inside a served job script returns the JOB's
#: communicator, and ``finalize()`` pops the job scope instead of
#: tearing the warm mesh down (job re-arm, not finalize-teardown)
_world_stack: list[Comm] = []


def init(mca_params: dict[str, str] | None = None) -> Comm:
    """MPI_Init: returns COMM_WORLD.

    ``mca_params`` are ``--mca key value`` pairs (highest precedence,
    like the mpirun command line). Idempotent once initialized (matching
    MPI-4 sessions' tolerant init), but params only apply on the first
    call.
    """
    global _world, _self_comm, _initialized
    if _initialized and _world is not None:
        return _world
    # MPI_DOUBLE / 64-bit ints are first-class datatypes.
    jax.config.update("jax_enable_x64", True)
    from ompi_tpu import compile_cache

    compile_cache.enable()
    from ompi_tpu.core import hooks, output

    hooks.fire("mpi_init_top")
    if mca_params:
        mca.init(mca_params)
    ctx = mca.default_context()
    ctx.open_all()
    output.register_verbose_var(ctx.store, "runtime")
    from ompi_tpu.tool import memchecker

    memchecker.register_var(ctx.store)
    memchecker.sync_from_store(ctx.store)
    # event tracing (--mca trace_enable 1): same register+sync shape as
    # memchecker — must precede ProcContext so DCN engine construction
    # is already on the timeline
    from ompi_tpu.trace import core as trace_core

    trace_core.register_vars(ctx.store)
    trace_core.sync_from_store(ctx.store)
    # cross-rank causal tracing (--mca trace_causal 1): wire-context
    # stamping + per-collective causal records; implies the tracer so
    # the offline critical-path report has events to read
    from ompi_tpu.trace import causal as _causal

    _causal.sync_from_store(ctx.store)
    # hang diagnosis (--mca hang_diag_enable, default ON): arm the
    # blocked-state registry before ProcContext so engine construction
    # forwards the gate to the C wait registry (tdcn_hang_diag)
    from ompi_tpu.trace import waitgraph as _waitgraph

    _waitgraph.sync_from_store(ctx.store)
    # transport telemetry (--mca metrics_enable 1): the quantitative
    # leg — native DCN counters + per-op histograms + flight recorder;
    # synced before ProcContext so engine construction already counts
    from ompi_tpu import metrics as _metrics

    _metrics.sync_from_store(ctx.store)
    # collective straggler profiler: armed with the metrics plane (or
    # by telemetry_enable alone — the live endpoint's straggler table
    # needs it even without a finalize export)
    from ompi_tpu.metrics import straggler as _straggler

    _straggler.sync_from_store(ctx.store)
    # fault injection (--mca faultsim_enable 1): armed before
    # ProcContext so engine bring-up (dials included) is already under
    # the plan; vars are centrally registered (core.var)
    from ompi_tpu import faultsim as _faultsim

    _faultsim.sync_from_store(ctx.store)
    from ompi_tpu.mesh.mesh import world_mesh

    wm = world_mesh()
    from ompi_tpu.boot.proc import launched_by_tpurun

    if launched_by_tpurun():
        # multi-process job (tpurun): this process owns a slice; the
        # world spans every process via the DCN (SURVEY.md §2.7)
        from ompi_tpu.boot.proc import ProcContext
        from .multiproc import MultiProcComm

        pc = ProcContext(local_size=wm.size)
        _world = MultiProcComm(pc, wm, name="MPI_COMM_WORLD")
        _self_comm = Comm(
            Group([_world.local_offset]), wm.submesh([0]), name="MPI_COMM_SELF"
        )
    else:
        _world = Comm(Group(range(wm.size)), wm, name="MPI_COMM_WORLD")
        _self_comm = Comm(Group([0]), wm.submesh([0]), name="MPI_COMM_SELF")
    from ompi_tpu.metrics import flight as _flight

    _flight.set_proc(int(getattr(_world, "proc", 0)))
    # live telemetry: start this rank's frame pump when the launcher
    # hosts an aggregator (tpurun sets OMPI_TPU_TELEMETRY_ADDR); a
    # disabled run opens no socket and starts no thread
    from ompi_tpu.metrics import live as _live

    _live.start_publisher(_world, ctx.store)
    # crash-path export: a rank that dies or aborts without reaching
    # finalize still flushes its configured metrics/trace outputs
    # (marked partial) — atexit covers aborts; the transports'
    # escalation paths call export.crash_dump directly for deaths
    # that bypass interpreter shutdown hooks
    _register_crash_flush()
    _initialized = True
    dev = wm.devices[0]
    output.verbose(1, "runtime",
                   "MPI_Init complete: world size %d (%s) on %d x %s "
                   "(platform %s)", _world.size, type(_world).__name__,
                   wm.size, dev.device_kind, dev.platform)
    hooks.fire("mpi_init_bottom", world=_world)
    return _world


_crash_flush_registered = False


def _register_crash_flush() -> None:
    """Register the atexit telemetry flush ONCE per interpreter: if
    the process exits while still initialized (sys.exit mid-job, an
    unhandled error, MPI_Abort-style teardown), the configured
    metrics/trace outputs are written with ``partial: true`` instead
    of vanishing with the rank.  A clean finalize leaves
    ``_initialized`` False, making the hook a no-op."""
    global _crash_flush_registered
    if _crash_flush_registered:
        return
    _crash_flush_registered = True
    import atexit

    def _flush():
        if _initialized:
            from ompi_tpu.metrics import export as _mexport

            _mexport.crash_dump("atexit")

    atexit.register(_flush)


def initialized() -> bool:
    return _initialized


# -- serve plane (tpud attach path) -------------------------------------


def push_world(comm) -> None:
    """Enter a job scope: ``comm`` becomes COMM_WORLD for code that
    calls :func:`init`/:func:`comm_world` until :func:`pop_world` —
    how a tpud resident worker runs an unmodified worker script in a
    warm mesh (the script's ``init()`` finds the job's communicator,
    its ``finalize()`` ends the job, not the daemon)."""
    global _world
    if _world is None:
        raise MPICommError("push_world before init")
    _world_stack.append(_world)
    _world = comm


def pop_world():
    """Leave the innermost job scope; returns the job comm that was
    active (idempotence guard: None when no scope is pushed)."""
    global _world
    if not _world_stack:
        return None
    job, _world = _world, _world_stack.pop()
    return job


def in_job_scope() -> bool:
    return bool(_world_stack)


def set_world(comm) -> None:
    """Replace the resident COMM_WORLD (the serve plane's repair path:
    after ``replace()`` restores a full-size communicator, future jobs
    must derive from the healed world, not the poisoned one)."""
    global _world
    if _world_stack:
        _world_stack[0] = comm
    else:
        _world = comm


def tpud_submit(url: str, script: str, args=(), tenant: str | None = None,
                wait: bool = True, timeout: float = 600.0) -> dict:
    """Attach-to-daemon client path: submit ``script`` to a running
    ``tpud`` at ``url`` and (by default) wait for its completion
    record — the warm-world sibling of launching a fresh ``tpurun``.
    Thin convenience over :mod:`ompi_tpu.serve.client`."""
    from ompi_tpu.serve import client as _client

    job = _client.submit(url, script, args=args, tenant=tenant)
    if wait:
        return _client.wait(url, job["id"], timeout=timeout)
    return job


def comm_world() -> Comm:
    if _world is None:
        raise MPICommError("call ompi_tpu.api.init() first")
    return _world


def comm_self() -> Comm:
    if _self_comm is None:
        raise MPICommError("call ompi_tpu.api.init() first")
    return _self_comm


def finalize() -> None:
    """MPI_Finalize: free the world objects and close frameworks.

    Inside a tpud job scope (:func:`push_world`) this is the JOB's
    finalize: the scope pops and the resident plane — mesh, engine
    threads, DCN endpoints, KVS connection, telemetry publisher —
    stays warm for the next job (the daemon's whole reason to exist).
    The worker loop frees the job communicator itself."""
    global _world, _self_comm, _initialized
    if _world_stack:
        pop_world()
        return
    from ompi_tpu.core import hooks

    hooks.fire("mpi_finalize_top", world=_world)
    # live telemetry: stop the frame pump before teardown (it sends
    # one final frame so the aggregator holds finalize-time counters)
    try:
        from ompi_tpu.metrics import live as _live

        _live.stop_publisher()
    except Exception:
        pass  # telemetry must never break finalize
    # spawned children: wait them out + drain their output while the
    # interpreter is fully alive (atexit alone races thread teardown)
    from .spawn import _reap

    _reap()
    # monitoring dump at finalize (≈ mca_pml_monitoring_dump via
    # common/monitoring when an output path is configured)
    try:
        out = mca.default_context().store.get("monitoring_base_output", "")
        if out:
            from ompi_tpu.tool import monitoring as _mon

            _mon.dump(str(out))
    except Exception:
        pass  # accounting must never break finalize
    # metrics export at finalize: every process writes
    # <metrics_output>.<proc>.prom (Prometheus text format) and
    # .jsonl (flight records + final snapshot) — analyze/correlate
    # with tools/metrics_report.py
    try:
        from ompi_tpu import metrics as _metrics

        mout = mca.default_context().store.get("metrics_output", "")
        if mout and _metrics.enabled():
            from ompi_tpu.metrics import export as _mexport

            _mexport.write(str(mout), proc=int(getattr(_world, "proc", 0)))
    except Exception:
        pass  # telemetry must never break finalize
    # trace dump at finalize (Chrome trace JSON; ≈ the monitoring dump
    # above): every process writes <trace_output>.<proc>.json — merge
    # with tools/trace_report.py --merge-out
    try:
        from ompi_tpu.trace import chrome as _tchrome, core as _tcore

        tout = mca.default_context().store.get("trace_output", "")
        if tout and _tcore.enabled():
            proc = int(getattr(_world, "proc", 0))
            _tchrome.dump(f"{tout}.{proc}.json", pid=proc)
    except Exception:
        pass  # tracing must never break finalize
    if _world is not None:
        pc = getattr(_world, "procctx", None)
        if pc is not None:
            pc.fence("finalize")  # all procs reach finalize before teardown
            pc.close()
        _world.free()
        _world = None
    if _self_comm is not None:
        _self_comm.free()
        _self_comm = None
    _initialized = False
    # a clean finalize wrote the real exports above — re-arm the
    # crash-path latch so a later init/death cycle can flush again
    try:
        from ompi_tpu.metrics import export as _mexport

        _mexport.reset_crash_latch()
    except Exception:
        pass
    mca.reset()
    hooks.fire("mpi_finalize_bottom")
