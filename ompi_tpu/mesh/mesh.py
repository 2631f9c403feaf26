"""Persistent device mesh — the fabric every communicator rides on.

TPU-native replacement for the reference's transport bring-up: where
``ompi_mpi_init`` opens BTLs and exchanges endpoints via PMIx
(SURVEY.md §3.2), here ``WorldMesh`` enumerates the job's devices ONCE
and pins a persistent ordering; every communicator owns a
``jax.sharding.Mesh`` over a subset of those devices with a single MPI
axis (``AXIS``).  Sub-communicators (comm_split) become sub-meshes over
the split device subsets — the analog of the CID + coll re-selection
path, with the device-order permutation hook playing the role of
``topo/treematch`` rank reordering.

This module is exposed through the MCA ``accelerator`` framework
(component ``accelerator/tpu`` ≈ the north star's ``opal/mca/
accelerator/tpu``), so device handling is selectable/configurable like
every other behavioral unit.
"""

from __future__ import annotations

import threading
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ompi_tpu.core import mca
from ompi_tpu.core.errors import MPIArgError, MPIInternalError
from ompi_tpu.core.registry import Component, register_component

#: the mesh axis name every communicator's collectives run over
AXIS = "mpi"


class CommMesh:
    """A communicator's view of the fabric: an ordered device list and
    the jax Mesh over it."""

    def __init__(self, devices: Sequence[jax.Device]):
        if len(devices) == 0:
            raise MPIArgError("empty device list")
        self.devices = tuple(devices)
        self.device_set = frozenset(self.devices)
        self.mesh = Mesh(np.array(self.devices, dtype=object), (AXIS,))
        self._sharding_cache: dict[tuple, NamedSharding] = {}
        from .arena import HbmArena

        #: staging manager (mpool/rcache analog — SURVEY.md §2.3)
        self.arena = HbmArena(self.devices)

    @property
    def size(self) -> int:
        return len(self.devices)

    # -- shardings ------------------------------------------------------

    def rank_sharding(self) -> NamedSharding:
        """Leading-axis-over-ranks sharding: rank r's buffer is the r-th
        slice, resident on device r. The canonical layout of every
        rank-major collective input."""
        return self._cached(("rank",), P(AXIS))

    def replicated_sharding(self) -> NamedSharding:
        return self._cached(("rep",), P())

    def _cached(self, key, spec) -> NamedSharding:
        s = self._sharding_cache.get(key)
        if s is None:
            s = NamedSharding(self.mesh, spec)
            self._sharding_cache[key] = s
        return s

    # -- staging (H2D/D2H; ≈ accelerator D2H/H2D + mpool arena) ---------

    def stage_in(self, host_array: np.ndarray) -> jax.Array:
        """Host rank-major (n, ...) buffer → device array sharded one
        rank per device, staged through the HBM arena."""
        if host_array.shape[0] != self.size:
            raise MPIArgError(
                f"rank-major buffer leading dim {host_array.shape[0]} != "
                f"comm size {self.size}"
            )
        return self.arena.stage_in(host_array, self.rank_sharding())

    def stage_out(self, device_array: jax.Array) -> np.ndarray:
        return np.asarray(jax.device_get(device_array))

    def submesh(self, indices: Sequence[int]) -> "CommMesh":
        """Sub-communicator mesh from local rank indices."""
        return CommMesh([self.devices[i] for i in indices])

    def __repr__(self) -> str:  # pragma: no cover
        kinds = {d.platform for d in self.devices}
        return f"<CommMesh {self.size} devices ({','.join(kinds)})>"


@register_component
class TpuAcceleratorComponent(Component):
    """``accelerator/tpu`` — device enumeration + world-mesh bring-up.

    ≈ the north star's new ``opal/mca/accelerator/tpu`` component. Runs on
    any XLA backend (TPU, or the virtual CPU platform used for
    oversubscribed-style testing, SURVEY.md §4).
    """

    FRAMEWORK = "accelerator"
    NAME = "tpu"
    PRIORITY = 50

    def __init__(self):
        super().__init__()
        self._world: CommMesh | None = None
        self._lock = threading.Lock()
        self._device_order: str = "default"

    def register_params(self, store) -> None:
        super().register_params(store)
        self._device_order = store.register(
            "accelerator",
            "tpu",
            "device_order",
            "default",
            help="Device ordering for COMM_WORLD ranks: 'default' (backend "
            "enumeration order, ICI-contiguous on TPU) or 'id' (sort by id)",
            enum=None,
        ).value
        store.register(
            "accelerator", "tpu", "donate_staged", True,
            help="Donate framework-staged input buffers to shape-"
            "preserving compiled collectives so XLA writes results into "
            "the same HBM allocation (mpool-style reuse; user jax "
            "arrays are never donated)",
        )

    def open(self, store) -> bool:
        try:
            return len(jax.devices()) > 0
        except Exception:
            return False

    def world_mesh(self) -> CommMesh:
        """The persistent job-wide mesh (created once, like the persistent
        ICI mesh the north star mandates)."""
        with self._lock:
            if self._world is None:
                devs = list(jax.devices())
                if self._device_order == "id":
                    devs.sort(key=lambda d: d.id)
                self._world = CommMesh(devs)
            return self._world


def world_mesh() -> CommMesh:
    """Module-level accessor: selected accelerator component's world mesh."""
    ctx = mca.default_context()
    fw = ctx.framework("accelerator")
    comp = fw.select_one()
    if not isinstance(comp, TpuAcceleratorComponent):  # future components
        if not hasattr(comp, "world_mesh"):
            raise MPIInternalError(
                f"accelerator component {comp.NAME} lacks world_mesh()"
            )
    return comp.world_mesh()
