"""Bring-up logic that decides what the chip run does, checked on the CPU:
one chip per tpurun rank, where the compile cache lives, which programs
bypass it, and chip_smoke.py's phases at tiny sizes."""

import importlib.util
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from ompi_tpu import compile_cache
from ompi_tpu.boot.tpurun import tpu_chip_count, worker_env

REPO = Path(__file__).resolve().parent.parent
TPU_KEYS = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
            "TPU_PROCESS_BOUNDS", "TPU_PROCESS_PORT", "TPU_PROCESS_ADDRESSES")


# -- one chip per rank -----------------------------------------------------

@pytest.fixture
def tpu_host_env(monkeypatch):
    """A launcher environment that does not pin JAX to the CPU."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    for k in TPU_KEYS:
        monkeypatch.delenv(k, raising=False)


def test_worker_env_binds_rank_r_to_chip_r(tpu_host_env):
    envs = [worker_env(r, 4, "kvs", tpu_chips=4, host_slot=(r, 4))
            for r in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_ADDRESSES"] == f"localhost:{e['TPU_PROCESS_PORT']}"
    # each rank is a slice of its own: distinct SliceBuilder ports
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4


@pytest.mark.parametrize("np_,chips", [(5, 4), (2, 1)])
def test_worker_env_rejects_more_ranks_than_chips(tpu_host_env, np_, chips):
    with pytest.raises(SystemExit,
                       match=f"{np_} ranks on a host with {chips} TPU chips"):
        worker_env(0, np_, "kvs", tpu_chips=chips, host_slot=(0, np_))


def test_worker_env_host_slot_binds_the_local_rank(tpu_host_env):
    e = worker_env(5, 8, "kvs", tpu_chips=4, host_slot=(1, 4))
    assert e["TPU_VISIBLE_CHIPS"] == "1"


@pytest.mark.parametrize("case", ["cpu_devices", "one_chip", "no_chip",
                                  "jax_platforms_cpu", "not_a_rank"])
def test_worker_env_binds_no_chip(tpu_host_env, monkeypatch, case):
    kw = {"tpu_chips": 4, "host_slot": (0, 1)}
    if case == "cpu_devices":
        kw["cpu_devices"] = 2
    elif case == "one_chip":
        kw["tpu_chips"] = 1  # the lone rank owns the host's only chip
    elif case == "no_chip":
        kw["tpu_chips"] = 0
    elif case == "not_a_rank":
        del kw["host_slot"]  # tpud's launch agent
    else:
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    e = worker_env(0, 1, "kvs", **kw)
    assert not any(k in e for k in TPU_KEYS)


def test_remote_rank_binds_its_chip_where_it_runs(tpu_host_env, monkeypatch):
    """plm/rsh: the launcher (a CPU head node here) binds nothing; the
    rank's slot rides the env and boot.bind binds it on its own host."""
    from ompi_tpu.boot import bind, tpurun
    from ompi_tpu.boot.proc import ENV_HOST_SLOT

    monkeypatch.setattr(tpurun, "tpu_chip_count", lambda: 0)
    e = worker_env(6, 8, "kvs", host_slot=(2, 4), remote=True)
    assert e[ENV_HOST_SLOT] == "2,4"
    assert not any(k in e for k in TPU_KEYS)
    # ... and on the remote host, which has four chips
    monkeypatch.setattr(bind, "tpu_chip_count", lambda: 4)
    remote_env = {ENV_HOST_SLOT: e[ENV_HOST_SLOT]}
    monkeypatch.setattr(bind.os, "environ", remote_env)
    execs = []
    monkeypatch.setattr(bind.os, "execvp", lambda f, a: execs.append(a))
    bind.main(["./a.out", "x"])
    assert execs == [["./a.out", "x"]]
    assert remote_env["TPU_VISIBLE_CHIPS"] == "2"
    assert ENV_HOST_SLOT not in remote_env


def test_tpud_agents_bind_host_local_ranks(tpu_host_env, monkeypatch):
    """tpud on 2 hosts x 4 chips, np=8: each host's agent binds its
    ranks to chips 0..3 of its own host."""
    import io

    from ompi_tpu.boot import tpurun
    from ompi_tpu.serve import agent as agent_mod

    monkeypatch.setattr(tpurun, "tpu_chip_count", lambda: 4)
    envs = {}

    class _Popen:
        def __init__(self, cmd, env, **kw):
            envs[int(env["OMPI_TPU_PROC"])] = env
            self.pid, self.stdout = 1, io.BytesIO()

    monkeypatch.setattr(agent_mod.subprocess, "Popen", _Popen)
    for hid, ranks in ((0, [0, 1, 2, 3]), (1, [4, 5, 6, 7])):
        ag = agent_mod.LaunchAgent.__new__(agent_mod.LaunchAgent)
        ag.hid, ag.np, ag.ranks, ag.kvs_addr = hid, 8, ranks, "kvs"
        ag._threads = []
        for r in ranks:
            ag._spawn_worker(r, 0)
    assert {r: e["TPU_VISIBLE_CHIPS"] for r, e in envs.items()} == {
        r: str(r % 4) for r in range(8)}


def _fake_host(root, pci_chips, nodes):
    """A device tree: ``pci_chips`` v5e chips (plus a gVNIC and a
    non-Google device) on the PCI bus, and ``nodes`` under /dev."""
    sysfs, dev = root / "pci", root / "dev"
    ids = [("0x1ae0", "0x0063")] * pci_chips + [("0x1ae0", "0x0042"),
                                               ("0x8086", "0x0063")]
    for i, (vendor, device) in enumerate(ids):
        d = sysfs / f"0000:00:{i:02x}.0"
        d.mkdir(parents=True)
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
    (dev / "vfio").mkdir(parents=True)
    (dev / "vfio" / "vfio").touch()  # the container node, not a chip
    for n in nodes:
        (dev / n).touch()
    return str(dev), str(sysfs)


@pytest.mark.parametrize("pci_chips,nodes,want", [
    (4, ["vfio/0"], 1),  # a one-chip machine on a four-chip host
    (4, ["vfio/0", "vfio/1", "vfio/2", "vfio/3"], 4),
    (4, ["accel0", "accel1"], 2),
    (0, ["vfio/0", "vfio/1"], 0),  # VFIO devices that are not TPUs
    (1, [], 0),
])
def test_tpu_chip_count_counts_the_chips_this_process_can_open(
        tmp_path, pci_chips, nodes, want):
    dev, sysfs = _fake_host(tmp_path, pci_chips, nodes)
    assert tpu_chip_count(dev, sysfs) == want


def test_one_openable_chip_binds_nothing(tpu_host_env, tmp_path,
                                         monkeypatch):
    """The lone rank of a one-chip machine takes whichever chip it was
    given, however many chips the host's PCI bus lists."""
    from ompi_tpu.boot import tpurun

    dev, sysfs = _fake_host(tmp_path, 4, ["vfio/0"])
    monkeypatch.setattr(tpurun, "tpu_chip_count",
                        lambda: tpu_chip_count(dev, sysfs))
    e = worker_env(0, 1, "kvs", host_slot=(0, 1))
    assert not any(k in e for k in TPU_KEYS)


# -- the compile cache -----------------------------------------------------

def test_cache_dir_is_the_environment_s_when_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.cache_dir() == tmp_path
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == tmp_path
        # JAX reads the variable itself: no other directory is set
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def test_cache_dir_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.cache_dir() == REPO / ".jax_cache"
    # a CPU-only process (the tests) keeps JAX's default of no cache
    assert jax.config.jax_platforms == "cpu"
    assert compile_cache.enable() is None
    assert jax.config.jax_compilation_cache_dir is None


class _Dev:
    def __init__(self, platform):
        self.platform = platform


@pytest.mark.parametrize("n,platform,want", [
    (2, "tpu", True),     # a Comm.split colour on a TPU host
    (8, "tpu", False),    # every chip: the world
    (1, "tpu", False),    # one chip: COMM_SELF
    (2, "cpu", False),    # virtual devices
])
def test_skips_cache_only_for_tpu_sub_slices(n, platform, want):
    assert len(jax.devices()) == 8
    assert compile_cache.skips_cache([_Dev(platform)] * n) is want


@pytest.fixture
def cache_on(tmp_path):
    """JAX's persistent cache on, in a private directory, for one test."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])
    cc.reset_cache()


@pytest.mark.parametrize("skip,written", [(True, 0), (False, 1)])
def test_guard_keeps_sub_slice_programs_out_of_the_cache(cache_on, monkeypatch,
                                                         skip, written):
    """A real jit over 2 of the 8 devices goes through JAX's own compile
    path to the guard (pinning the hook to the installed JAX): a
    sub-slice program is neither read from nor written to the cache,
    every other program is cached."""
    from jax._src import compiler
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    monkeypatch.setattr(compiler, "_get_cache_key",
                        compiler._get_cache_key)  # restore after
    asked = []
    monkeypatch.setattr(compile_cache, "skips_cache",
                        lambda devices: asked.append(len(devices)) or skip)
    compile_cache._guard_sub_slices()
    compile_cache._guard_sub_slices()  # once per process
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    x = jax.device_put(np.arange(16.0), NamedSharding(mesh, P("x")))
    out = jax.jit(lambda v: v * 3 + 1)(x)
    np.testing.assert_array_equal(np.asarray(out), np.arange(16.0) * 3 + 1)
    assert asked == [2]
    assert compile_cache.entries(cache_on) == written


# -- chip_smoke.py ---------------------------------------------------------

def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def world(devices):
    import ompi_tpu.api as api

    return api.init()


@pytest.fixture
def reproducible():
    """Fix the fold order: on 8 CPU ranks psum's order is XLA's, and the
    phases compare float sums bit-exactly with the rank-ordered fold."""
    from ompi_tpu.core import mca

    store = mca.default_context().store
    store.set("coll_xla_reproducible", True)
    yield
    store.set("coll_xla_reproducible", False)


PHASES = {
    "allreduce_exact": lambda s, w: s.allreduce_exact(w, 4096),
    "op_dtype_matrix": lambda s, w: {"cases": s.op_dtype_matrix(w, 512)},
    "collectives": lambda s, w: {"cases": s.collectives(w, 4096)},
    "nonblocking": lambda s, w: {"cases": s.nonblocking(w, 4096)},
    "host_path": lambda s, w: s.host_path(w, 4096),
    "world_vs_psum": lambda s, w: s.world_vs_psum(w, 4096),
    "pallas_ring_allreduce": lambda s, w: s.pallas_ring(w, "allreduce", 4096),
    "pallas_ring_allgather": lambda s, w: s.pallas_ring(w, "allgather", 4096),
    "pallas_ring_reduce_scatter": lambda s, w: s.pallas_ring(w, "reduce_scatter", 4096),
    "split_colors": lambda s, w: s.split_colors(w, 4096),
}


@pytest.fixture
def smoke_sees_dma(monkeypatch):
    """The pallas_ring phases insist on the DMA hop, which only a TPU
    runs: the phase's own check reads "dma", and every later call (the
    hop, as it traces) still gets the CPU's emulated ppermute."""
    from ompi_tpu.coll import pallas_kernels

    modes = iter(["dma"])
    monkeypatch.setattr(pallas_kernels, "mode",
                        lambda: next(modes, "emulate"))


@pytest.mark.parametrize("name", sorted(PHASES))
def test_smoke_phase_on_the_cpu_mesh(smoke, world, reproducible, name,
                                     smoke_sees_dma, capsys):
    if name == "world_vs_psum":  # the phase toggles the mode itself
        from ompi_tpu.core import mca

        mca.default_context().store.set("coll_xla_reproducible", False)
    rec = smoke.run_phase(name, PHASES[name], smoke, world)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith(f"[phase] {name} ")
    printed = json.loads(line.split(" ", 2)[2])
    assert printed["ok"] is True and rec["ok"] is True
    assert printed["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": 8}


def test_smoke_phase_failure_raises(smoke, world):
    """Off the TPU the hop is emulated, and the pallas_ring phase says so."""
    with pytest.raises(smoke.SmokeError, match="emulate, not dma"):
        smoke.pallas_ring(world, "allreduce", 4096)


def test_smoke_main_fails_without_a_chip(smoke, capsys):
    assert smoke.main([]) != 0
    assert smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out
    assert "this host has 0" in out.err


def test_smoke_fails_outside_the_repo(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo."""
    import shutil
    import subprocess

    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
