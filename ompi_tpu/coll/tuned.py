"""``coll/tuned`` — the algorithm decision layer.

TPU-native re-design of ``ompi/mca/coll/tuned`` (SURVEY.md §2.2:
"default intra-node+inter-node algorithm chooser; fixed decision rules +
dynamic rule files", [bin] ``coll_tuned_<coll>_algorithms`` enums,
decision entry ``ompi_coll_tuned_allreduce_intra_dec_fixed`` in the
MPI_Allreduce call stack, SURVEY.md §3.3).

Exactly like the reference, tuned implements **no algorithms of its
own**: it chooses one per call from the shared library — here the
``coll/xla`` module's compiled-program factory over ``coll.base`` — and
delegates.  The choice is keyed on (communicator size, per-rank message
size), through two sources:

* **fixed rules** (:func:`fixed_decision`): the built-in decision
  functions.  The reference's tables encode TCP/shared-memory crossover
  points; ours encode the TPU fabric's: the fused XLA primitive
  (psum/all_gather/…) is optimal at virtually every size because ICI
  collectives are hardware-routed, so the fixed rules pick the direct
  path UNCONDITIONALLY whenever the op allows (no size cutover — a
  hardware-routed collective beats any software schedule), and fall to
  ordered / segmented schedules only where semantics (non-commutative
  ops; bit-exact mode is enforced inside coll/xla itself) or HBM
  staging of very large software-op buffers demand;
* **dynamic rules** (``--mca coll_tuned_use_dynamic_rules 1`` +
  ``coll_tuned_dynamic_rules_filename``): the reference's rule-file
  format, parsed by :func:`parse_rules_file` — per collective id, per
  communicator-size bracket, (msg_size, algorithm, topo_faninout,
  segsize) rows; the largest bracket ≤ the actual size applies.
  Algorithm numbers are this framework's enums (coll/xla's tables),
  documented by ``python -m ompi_tpu info --all``.

Stacking: PRIORITY 95 places tuned above coll/xla (90) exactly as the
reference places tuned above basic — tuned wins every slot xla can
serve and drives xla's machinery through the forced-override hook.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from ompi_tpu.core.errors import MPIArgError
from ompi_tpu.core.registry import Component, register_component
from ompi_tpu.op.op import Op
from ompi_tpu.trace import core as _trace
from .module import COLL_OPS, CollModule
from .xla import (
    ALLGATHER_ALGOS,
    ALLREDUCE_ALGOS,
    ALLTOALL_ALGOS,
    BARRIER_ALGOS,
    BCAST_ALGOS,
    REDUCE_ALGOS,
    REDUCE_SCATTER_ALGOS,
    XlaCollModule,
)

# Collective ids in the reference's dynamic-rule files
# (ompi/mca/coll/base/coll_base_functions.h COLLCOUNT order).
COLL_IDS = {
    "allgather": 0,
    "allgatherv": 1,
    "allreduce": 2,
    "alltoall": 3,
    "alltoallv": 4,
    "alltoallw": 5,
    "barrier": 6,
    "bcast": 7,
    "exscan": 8,
    "gather": 9,
    "gatherv": 10,
    "reduce": 11,
    "reduce_scatter": 12,
    "reduce_scatter_block": 13,
    "scan": 14,
    "scatter": 15,
    "scatterv": 16,
}

#: which algorithm-enum var each collective's decision drives
_ALGO_VAR = {
    "allreduce": ("allreduce_algorithm", ALLREDUCE_ALGOS),
    "bcast": ("bcast_algorithm", BCAST_ALGOS),
    "reduce": ("reduce_algorithm", REDUCE_ALGOS),
    "allgather": ("allgather_algorithm", ALLGATHER_ALGOS),
    "gather": ("allgather_algorithm", ALLGATHER_ALGOS),
    "alltoall": ("alltoall_algorithm", ALLTOALL_ALGOS),
    "reduce_scatter": ("reduce_scatter_algorithm", REDUCE_SCATTER_ALGOS),
    "reduce_scatter_block": ("reduce_scatter_algorithm", REDUCE_SCATTER_ALGOS),
    "barrier": ("barrier_algorithm", BARRIER_ALGOS),
}

#: ops whose first positional argument is the reduction-op-carrying call
_HAS_OP = {"allreduce", "reduce", "reduce_scatter", "reduce_scatter_block",
           "scan", "exscan"}

#: coll_id → valid algorithm ids (0 = "use the fixed decision")
_VALID_ALGS = {
    COLL_IDS[name]: set(enum.values()) for name, (_, enum) in _ALGO_VAR.items()
}


class RuleSet:
    """Parsed dynamic rules: coll_id → [(comm_size, [(msg, alg, fanio,
    segsize)])], both levels sorted ascending."""

    def __init__(self, rules: dict[int, list[tuple[int, list[tuple[int, int, int, int]]]]]):
        self.rules = rules

    def lookup(self, coll: str, comm_size: int, msg_bytes: int) -> tuple[int, int] | None:
        """(algorithm, segsize) from the best-matching rule, or None.
        Bracket selection matches the reference: the largest registered
        comm size ≤ actual, then the largest msg size ≤ actual; an
        algorithm of 0 means "fall back to the fixed decision"."""
        per_coll = self.rules.get(COLL_IDS.get(coll, -1))
        if not per_coll:
            return None
        bracket = None
        for size, msg_rules in per_coll:
            if size <= comm_size:
                bracket = msg_rules
        if bracket is None:
            return None
        chosen = None
        for msg, alg, _fanio, segsize in bracket:
            if msg <= msg_bytes:
                chosen = (alg, segsize)
        if chosen is None or chosen[0] == 0:
            return None
        return chosen


def parse_rules_file(text: str) -> RuleSet:
    """Parse the reference's coll_tuned dynamic rules format:

    ``n_collectives`` then per collective: ``coll_id``,
    ``n_comm_sizes``, then per comm size: ``comm_size``,
    ``n_msg_rules``, then per rule: ``msg_size alg faninout segsize``.
    ``#``-comments and blank lines allowed anywhere.
    """
    toks: list[int] = []
    for line in text.splitlines():
        line = line.split("#", 1)[0]
        for t in line.split():
            try:
                toks.append(int(t))
            except ValueError as e:
                raise MPIArgError(f"bad token {t!r} in rules file") from e
    it = iter(toks)

    def nxt(what: str) -> int:
        try:
            return next(it)
        except StopIteration:
            raise MPIArgError(f"rules file truncated reading {what}") from None

    rules: dict[int, list[tuple[int, list[tuple[int, int, int, int]]]]] = {}
    n_coll = nxt("collective count")
    for _ in range(n_coll):
        cid = nxt("collective id")
        n_sizes = nxt("comm-size count")
        brackets = []
        for _ in range(n_sizes):
            csize = nxt("comm size")
            n_rules = nxt("rule count")
            rows = []
            for _ in range(n_rules):
                rows.append((nxt("msg"), nxt("alg"), nxt("fanio"), nxt("segsize")))
                valid = _VALID_ALGS.get(cid)
                if valid is not None and rows[-1][1] not in valid:
                    raise MPIArgError(
                        f"rules file names algorithm {rows[-1][1]} for "
                        f"collective id {cid}; valid ids: {sorted(valid)}"
                    )
            rows.sort(key=lambda r: r[0])
            brackets.append((csize, rows))
        brackets.sort(key=lambda b: b[0])
        rules[cid] = brackets
    return RuleSet(rules)


def fixed_decision(coll: str, comm_size: int, msg_bytes: int, op: Op | None,
                   large: int, huge: int) -> tuple[int | None, int | None]:
    """The fixed decision tables (≈ ompi_coll_tuned_*_intra_dec_fixed).

    Returns (algorithm id or None for the module default, segcount or
    None).  ``large``/``huge`` are the byte thresholds from the
    ``coll_tuned_large_msg`` / ``coll_tuned_huge_msg`` vars.
    """
    if coll == "allreduce":
        # Fabric-reducible commutative ops take the fused primitive at
        # EVERY size: a hardware-routed psum/pmax cannot be beaten by a
        # software ppermute schedule, so (unlike the reference's TCP
        # crossovers) there is no large-message cutover for them — the
        # size ladder below applies to software ops only.  Bit-exact
        # mode needs no branch here: coll_xla_reproducible overrides any
        # forced algorithm inside the xla module itself.
        assert op is not None
        if op.lax_collective is not None and op.commutative:
            return ALLREDUCE_ALGOS["psum"], None
        if not op.commutative:
            return ALLREDUCE_ALGOS["ordered_linear"], None
        if msg_bytes >= huge:
            # software-op huge messages: the device-DMA ring keeps the
            # chunk rotation in HBM with explicit semaphores — chosen
            # when the Pallas leg can actually lower (TPU backend);
            # the segmented host ring stays the CPU/GPU answer
            from . import pallas_kernels as _pk

            if _pk.dma_available():
                return ALLREDUCE_ALGOS["pallas_ring"], None
            return ALLREDUCE_ALGOS["ring_segmented"], None
        if msg_bytes >= large:
            # Rabenseifner needs pow2 (xla falls back to ring otherwise)
            return ALLREDUCE_ALGOS["rabenseifner"], None
        return ALLREDUCE_ALGOS["recursive_doubling"], None
    if coll == "bcast":
        if msg_bytes >= huge:
            return BCAST_ALGOS["pipeline"], None
        return BCAST_ALGOS["direct"], None
    if coll == "reduce":
        if op is not None and not op.commutative:
            return REDUCE_ALGOS["ordered"], None
        return REDUCE_ALGOS["binomial"], None
    if coll in ("allgather", "gather"):
        if msg_bytes >= huge:
            return ALLGATHER_ALGOS["ring"], None
        return ALLGATHER_ALGOS["direct"], None
    if coll == "alltoall":
        if msg_bytes >= huge:
            return ALLTOALL_ALGOS["pairwise"], None
        return ALLTOALL_ALGOS["direct"], None
    if coll in ("reduce_scatter", "reduce_scatter_block"):
        if op is not None and op.lax_collective == "psum":
            return REDUCE_SCATTER_ALGOS["direct"], None
        if op is not None and not op.commutative:
            return REDUCE_SCATTER_ALGOS["ordered"], None
        return REDUCE_SCATTER_ALGOS["ring"], None
    if coll == "barrier":
        return (BARRIER_ALGOS["dissemination"] if comm_size > 16
                else BARRIER_ALGOS["allreduce"]), None
    return None, None


#: DCN-plane schedule ids for the C collective fast path (shared with
#: native/src/dcn.cc's CollAlgo and the shim's tdcn_coll_plan calls)
DCN_LINEAR, DCN_RING = 0, 1


def dcn_fixed_decision(coll: str, comm_size: int, msg_bytes: int,
                       op: Op | None, ring_threshold: int,
                       reproducible: bool = False) -> int:
    """The decision layer's verdict for a DCN-plane (inter-process)
    schedule — the fixed rules behind the C collective fast path's
    compiled plans (tdcn_coll_plan's ``algo``), mirroring the
    crossover ``dcn/collops`` applies per call so the two planes pick
    one schedule bit-for-bit:

    * only ``allreduce`` has a ring variant; every other C-served
      collective is linear;
    * reproducible mode (``coll_han_reproducible``) pins the
      process-ordered linear fold;
    * the ring needs a commutative op (its per-chunk fold order walks
      the ring, not rank order) and ``msg_bytes`` at or above the
      engine's ring crossover.
    """
    del comm_size  # the DCN crossover is size-in-bytes driven
    if coll != "allreduce" or reproducible:
        return DCN_LINEAR
    if op is not None and not getattr(op, "commutative", False):
        return DCN_LINEAR
    return DCN_RING if msg_bytes >= ring_threshold else DCN_LINEAR


class TunedCollModule(CollModule):
    """Per-communicator decision module: wraps the comm's coll/xla
    module and forces its per-call algorithm choice through
    :meth:`XlaCollModule.forced`."""

    def __init__(self, comm, component: "TunedCollComponent", inner: XlaCollModule):
        super().__init__(comm)
        self.component = component
        self.inner = inner

    # tuned provides exactly the slots its delegate provides
    def provided(self) -> dict[str, Any]:
        out = {}
        for slot, fn in self.inner.provided().items():
            out[slot] = self._make_wrapper(slot, fn)
        return out

    def enable(self) -> None:
        self.inner.enable()

    @staticmethod
    def _base_op(slot: str) -> str:
        if slot.endswith("_init"):
            return slot[: -len("_init")]
        if slot.startswith("i") and slot[1:] in COLL_OPS:
            return slot[1:]
        return slot

    def _make_wrapper(self, slot: str, fn):
        base = self._base_op(slot)

        def wrapper(*args, **kwargs):
            overrides = self._decide(base, args, kwargs)
            with self.inner.forced(**overrides):
                return fn(*args, **kwargs)

        wrapper.__name__ = f"tuned_{slot}"
        return wrapper

    def resolve(self, base: str, *args, donate: bool = False,
                recycle: bool = False):
        """Fast-path resolution: run the decision once for this call
        signature, then hand the forced choice to the inner module's
        resolver.  The compiled callable the api layer caches therefore
        BAKES IN tuned's decision — valid until the var store changes
        (the cache keys on the store version)."""
        overrides = self._decide(base, args, {})
        with self.inner.forced(**overrides):
            return self.inner.resolve(base, *args, donate=donate,
                                      recycle=recycle)

    def _decide(self, coll: str, args, kwargs) -> dict[str, int]:
        var_enum = _ALGO_VAR.get(coll)
        if var_enum is None:
            return {}
        var, enum = var_enum
        store = self.component.store
        # an explicitly pinned coll_xla_*_algorithm (non-auto) bypasses
        # the decision layer — the reference's "algorithm 0 = let the
        # decision function choose" convention
        if int(store.get(f"coll_xla_{var}", 0)) != 0:
            return {}
        n = self.comm.size
        # per-rank message bytes from the rank-major buffer, if any
        msg_bytes = 0
        if args:
            x = args[0]
            nbytes = getattr(x, "nbytes", None)
            if nbytes is None:
                nbytes = np.asarray(x).nbytes
            msg_bytes = int(nbytes) // max(n, 1)
        op = None
        if coll in _HAS_OP:
            op = kwargs.get("op")
            if op is None and len(args) > 1 and isinstance(args[1], Op):
                op = args[1]
        # dynamic rules first (an explicit rule wins, as in the reference)
        if self.component.ruleset is not None:
            hit = self.component.ruleset.lookup(coll, n, msg_bytes)
            if hit is not None:
                alg, segsize = hit  # id validity enforced at parse time
                out = {var: alg}
                if segsize:
                    # file segsize is in bytes; segcount is elements —
                    # element size is unknown here, divide by 4 (the
                    # reference's rule files are likewise written
                    # against an assumed datatype)
                    out["segcount"] = max(1, segsize // 4)
                if _trace._enabled:
                    self._trace_decision(coll, n, msg_bytes, enum, alg,
                                         "dynamic")
                return out
        large = int(store.get("coll_tuned_large_msg", 1 << 20))
        huge = int(store.get("coll_tuned_huge_msg", 64 << 20))
        alg, seg = fixed_decision(coll, n, msg_bytes, op, large, huge)
        out: dict[str, int] = {}
        if alg is not None:
            out[var] = alg
        if seg is not None:
            out["segcount"] = seg
        if _trace._enabled and alg is not None:
            self._trace_decision(coll, n, msg_bytes, enum, alg, "fixed")
        return out

    @staticmethod
    def _trace_decision(coll: str, n: int, msg_bytes: int, enum, alg: int,
                        source: str) -> None:
        """Timeline record of which algorithm this decision picked —
        the per-call answer to "which schedule did tuned choose" that
        aggregate counters cannot give."""
        name = next((k for k, v in enum.items() if v == alg), str(alg))
        _trace.instant("coll", "tuned_decision", coll=coll, comm_size=n,
                       msg_bytes=msg_bytes, algorithm=name, source=source)


@register_component
class TunedCollComponent(Component):
    FRAMEWORK = "coll"
    NAME = "tuned"
    PRIORITY = 95  # above xla (90): tuned is the default decision layer

    def __init__(self):
        super().__init__()
        self.store = None
        self.ruleset: RuleSet | None = None

    def register_params(self, store) -> None:
        super().register_params(store)
        self.store = store
        store.register(
            "coll", "tuned", "use_dynamic_rules", False,
            help="Consult the dynamic rules file before fixed decisions",
        )
        store.register(
            "coll", "tuned", "dynamic_rules_filename", "", type="string",
            help="Path to a coll_tuned-format dynamic rules file",
        )
        store.register(
            "coll", "tuned", "large_msg", 1 << 20, type="int",
            help="Per-rank bytes above which large-message algorithms kick in",
        )
        store.register(
            "coll", "tuned", "huge_msg", 64 << 20, type="int",
            help="Per-rank bytes above which segmented/pipelined "
            "algorithms kick in (HBM staging relief)",
        )

    def open(self, store) -> bool:
        self.ruleset = None
        if store.get("coll_tuned_use_dynamic_rules", False):
            path = str(store.get("coll_tuned_dynamic_rules_filename", ""))
            if path:
                try:
                    with open(path) as f:
                        self.ruleset = parse_rules_file(f.read())
                except (OSError, MPIArgError) as e:
                    # the reference warns and continues on fixed decisions
                    # (a raise here would silently drop the whole component:
                    # Framework.open treats component exceptions as
                    # "unusable")
                    import warnings

                    warnings.warn(
                        f"coll/tuned: ignoring dynamic rules file {path}: {e}",
                        RuntimeWarning,
                        stacklevel=2,
                    )
                    self.ruleset = None
        return True

    def query(self, comm, table=None) -> TunedCollModule | None:
        # tuned serves wherever xla serves: wrap the comm's xla module,
        # already stacked at lower priority in the partially built table.
        if table is None:
            return None
        inner = next(
            (m for m in table.modules if isinstance(m, XlaCollModule)), None
        )
        if inner is None:
            return None
        return TunedCollModule(comm, self, inner)

    query.wants_table = True
