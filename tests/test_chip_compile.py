"""Compile the main path's device programs for a described TPU v5e 2x2
slice — no chip attached: what the chip's compiler refuses fails here,
at no chip time.  Nothing runs, so nothing here is a result or a time.

The three ``pallas_ring`` families compile with the DMA hop kernel
(``tpu_custom_call``) at 4 MiB and 256 MiB per rank; the world ``psum``
allreduce and the ``coll_xla_reproducible`` ordered allreduce compile
at 256 MiB per rank; every program must fit one chip's HBM.
"""

import numpy as np
import pytest

MiB = 1 << 20
#: v5e HBM a program may use (the TPU compiler's own limit: 15.75G)
V5E_HBM = int(15.75 * (1 << 30))
N = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def mesh(topo):
    """The 4-chip mesh, with JAX's persistent cache off: a compile for a
    described chip is written to it but can never be read back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import Mesh

    from ompi_tpu.mesh import AXIS

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield Mesh(np.array(topo.devices, dtype=object), (AXIS,))
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(mesh, per_device, shape, vma: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ompi_tpu.mesh import AXIS

    f = jax.jit(jax.shard_map(per_device, mesh=mesh, in_specs=P(AXIS),
                              out_specs=P(AXIS), check_vma=vma))
    x = jax.ShapeDtypeStruct(shape, jnp.float32,
                             sharding=NamedSharding(mesh, P(AXIS)))
    c = f.lower(x).compile()
    ma = c.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < total <= V5E_HBM, f"{total} B does not fit one v5e chip"
    return c.as_text(), ma


def _pallas_case(fam: str, nbytes: int):
    """(per-device fn, global shape) exactly as coll/xla builds them."""
    from ompi_tpu.coll import pallas_kernels as pk
    from ompi_tpu.op import SUM

    count = nbytes // 4
    if fam == "allreduce":
        return (lambda v: pk.ring_allreduce(v[0], SUM, N, _mode="dma")[None],
                (N, count))
    if fam == "allgather":
        return (lambda v: pk.ring_allgather(v[0], N, _mode="dma")[None],
                (N, count))
    return (lambda v: pk.ring_reduce_scatter(v[0], SUM, N, _mode="dma")[None],
            (N, N, count // N))


@pytest.mark.parametrize("nbytes", [4 * MiB, 256 * MiB], ids=["4MiB", "256MiB"])
@pytest.mark.parametrize("fam", ["allreduce", "allgather", "reduce_scatter"])
def test_pallas_ring_compiles_with_dma_kernel(mesh, fam, nbytes):
    fn, shape = _pallas_case(fam, nbytes)
    text, ma = _compile(mesh, fn, shape)
    # one kernel per ring hop: 2(n-1) for allreduce, n-1 otherwise
    hops = 2 * (N - 1) if fam == "allreduce" else N - 1
    assert text.count("tpu_custom_call") >= hops
    assert ma.argument_size_in_bytes == nbytes  # per device


@pytest.mark.parametrize("algo", ["psum", "ordered_linear"])
def test_world_allreduce_compiles_at_256MiB(mesh, algo):
    from ompi_tpu.coll import base as algos
    from ompi_tpu.op import SUM

    impl = {"psum": algos.allreduce_psum,
            "ordered_linear": algos.allreduce_ordered_linear}[algo]
    text, ma = _compile(mesh, lambda v: impl(v[0], SUM, N)[None],
                        (N, 256 * MiB // 4), vma=True)
    assert "tpu_custom_call" not in text
    assert ma.argument_size_in_bytes == 256 * MiB
