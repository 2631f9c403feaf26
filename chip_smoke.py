"""Bring-up smoke on the chip: drive the MPI collective path once through
the entry points a user calls, check every result, print one JSON line.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the paths that exist only across chips

Each phase prints ``[phase] <name> {json}``; the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  A phase that
fails prints its traceback and the script exits non-zero with no result
line.  It refuses to run anywhere but a TPU: the C-ABI phase runs first,
before this process touches JAX, so its child owns the chip.

Times here are bring-up evidence (``compile_s`` is the first call minus a
warm call; ``run_s`` is one warm call timed to ``block_until_ready``),
not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import re
import subprocess
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
KiB, MiB = 1 << 10, 1 << 20
#: per-rank fp32 allreduce sizes: 1 GiB is BASELINE's top osu size
ONE_CHIP_SIZES = (8, 4 * KiB, MiB, 64 * MiB, 1 << 30)
FOUR_CHIP_SIZES = (8, 4 * KiB, MiB, 64 * MiB, 256 * MiB)
PALLAS_SIZES = (4 * MiB, 256 * MiB)
OSU_MAX_BYTES, OSU_ITERS = MiB, 50


class SmokeError(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def emit(name: str, rec: dict) -> None:
    print(f"[phase] {name} {json.dumps(rec, sort_keys=True)}", flush=True)


def _peak_bytes() -> int | None:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def _device() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def run_phase(name: str, fn, *args, **kwargs) -> dict:
    """One phase: run ``fn`` (which raises on a wrong result), stamp the
    record with the device and its peak memory, print it."""
    t0 = time.perf_counter()
    rec = fn(*args, **kwargs)
    rec = {"ok": True, **rec, "wall_s": round(time.perf_counter() - t0, 4)}
    if "device" not in rec:
        rec["device"] = _device()
        rec["peak_bytes_in_use"] = _peak_bytes()
    emit(name, rec)
    return rec


def timed(fn, *args):
    """(result, compile_s, run_s): the first call compiles, the second
    is the warm call; both end in ``block_until_ready``."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    run = time.perf_counter() - t0
    return out, round(max(first - run, 0.0), 6), round(run, 6)


def bits_equal(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and np.array_equal(
        a.view(np.uint8), b.view(np.uint8))


def rank_data(n: int, nbytes: int, dtype, seed: int):
    """(n, nbytes/itemsize) rank-major host data made from ``seed``."""
    import numpy as np

    dt = np.dtype(dtype)
    count = max(1, nbytes // dt.itemsize)
    rng = np.random.default_rng(seed)
    if dt.kind == "i":
        return rng.integers(-1000, 1000, (n, count), dtype=dt)
    if dt == np.float32:
        return rng.standard_normal((n, count), dtype=np.float32)
    return rng.standard_normal((n, count), dtype=np.float32).astype(dt)


def staged(comm, host):
    import jax

    return jax.device_put(host, comm.mesh.rank_sharding())


# -- C ABI (runs before this process touches JAX) -------------------------

def build_osu() -> tuple[Path, float]:
    """Rebuild the native libraries from the committed sources in this
    checkout (a copied ``native/build`` may point at another tree), then
    compile the stock OSU-style benchmark with the mpicc wrapper."""
    from ompi_tpu import native

    t0 = time.perf_counter()
    native.build(force=True)
    out = native.BUILD_DIR / "chip_smoke_osu_allreduce"
    subprocess.run([sys.executable, "-m", "ompi_tpu", "mpicc",
                    str(REPO / "native" / "bench" / "osu_allreduce.c"),
                    "-o", str(out)], check=True, cwd=REPO)
    return out, round(time.perf_counter() - t0, 3)


_INIT_LINE = re.compile(r"^\[(\d+)\] \[ompi_tpu:runtime\] MPI_Init complete: "
                        r"world size (\d+) \(\w+\) on (\d+) x (.+) "
                        r"\(platform (\w+)\)$")
_OSU_ROW = re.compile(r"^\[0\] (\d+)\s+([0-9.]+)\s*$")


def osu_sizes(max_bytes: int) -> list[int]:
    sizes, b = [], 4
    while b <= max_bytes:
        sizes.append(b)
        b *= 4
    return sizes


def capi_osu(np_: int, timeout: float = 600) -> dict:
    """``tpurun -np N`` of the C osu_allreduce with no --cpu-devices: on
    a TPU host each rank binds a chip of its own.  The binary aborts on
    a wrong sum, so a full set of size rows means every size validated;
    each rank's MPI_Init line names the device it ran on."""
    from ompi_tpu.boot.tpurun import tpu_chip_count

    binary, build_s = build_osu()
    cmd = [sys.executable, "-m", "ompi_tpu", "run", "-np", str(np_),
           "--mca", "runtime_base_verbose", "1", str(binary),
           str(OSU_MAX_BYTES), str(OSU_ITERS)]
    r = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                       timeout=timeout)
    lines = r.stdout.splitlines()
    check(r.returncode == 0,
          f"tpurun rc={r.returncode}:\n{r.stdout[-4000:]}\n{r.stderr[-4000:]}")
    inits = {}
    for ln in lines:
        m = _INIT_LINE.match(ln)
        if m:
            inits[int(m[1])] = {"world": int(m[2]), "local_devices": int(m[3]),
                                "kind": m[4], "platform": m[5]}
    check(sorted(inits) == list(range(np_)),
          f"MPI_Init lines from ranks {sorted(inits)}, want 0..{np_ - 1}")
    rows = {int(m[1]): float(m[2]) for m in map(_OSU_ROW.match, lines) if m}
    check(sorted(rows) == osu_sizes(OSU_MAX_BYTES),
          f"osu rows {sorted(rows)} != {osu_sizes(OSU_MAX_BYTES)}")
    platforms = sorted({v["platform"] for v in inits.values()})
    check(platforms == ["tpu"], f"C ranks ran on {platforms}, not tpu")
    check(all(v["local_devices"] == 1 and v["world"] == np_
              for v in inits.values()),
          f"ranks must each hold one chip of a {np_}-rank world: {inits}")
    return {"binary": "native/bench/osu_allreduce.c", "np": np_,
            "host_chips": tpu_chip_count(), "build_s": build_s,
            "child_platform": platforms[0],
            "ranks": inits, "sizes": f"4B..{OSU_MAX_BYTES}B",
            "avg_us": rows, "verdict": "binary validated every size",
            "device": {"platform": platforms[0],
                       "kind": inits[0]["kind"], "count": np_},
            "peak_bytes_in_use": None}


# -- the api path on the device mesh -------------------------------------

def allreduce_exact(world, nbytes: int, seed: int = 1) -> dict:
    """fp32 MPI_SUM, every rank bit-exact against the rank-ordered host
    fold (on n > 1 ranks only the reproducible path fixes that order)."""
    from ompi_tpu.op import SUM, ordered_reduce_np

    x = rank_data(world.size, nbytes, "float32", seed)
    out, c, r = timed(world.allreduce, staged(world, x), SUM)
    golden = ordered_reduce_np(x, SUM)
    check(all(bits_equal(out[i], golden) for i in range(world.size)),
          f"allreduce {nbytes}B not bit-exact vs ordered_reduce_np")
    return {"op": "SUM", "dtype": "float32", "bytes_per_rank": nbytes,
            "compile_s": c, "run_s": r,
            "verdict": "bit-exact vs ordered_reduce_np"}


MATRIX_OPS = ("SUM", "MAX", "MIN", "PROD")
MATRIX_DTYPES = ("bfloat16", "float32", "int32")


def op_dtype_matrix(world, nbytes: int, seed: int = 2) -> list[dict]:
    """BASELINE's {SUM, MAX, MIN, PROD} x {bf16, fp32, int32} against
    the numpy fold of the host input."""
    import jax.numpy as jnp

    from ompi_tpu import op as ops

    recs = []
    for opname in MATRIX_OPS:
        op = getattr(ops, opname)
        for dt in MATRIX_DTYPES:
            x = rank_data(world.size, nbytes, jnp.dtype(dt), seed)
            out, c, r = timed(world.allreduce, staged(world, x), op)
            golden = ops.ordered_reduce_np(x, op)
            for row in range(world.size):
                check(bits_equal(out[row], golden),
                      f"{opname} {dt} rank {row} != numpy")
            recs.append({"op": opname, "dtype": dt, "bytes_per_rank": nbytes,
                         "compile_s": c, "run_s": r,
                         "verdict": "exact vs numpy on the host input"})
    return recs


def collectives(world, nbytes: int, seed: int = 3) -> list[dict]:
    """bcast / allgather / reduce_scatter_block / alltoall, int32,
    exact against numpy."""
    import numpy as np

    from ompi_tpu.op import SUM

    n = world.size
    x = rank_data(n, nbytes, "int32", seed)
    blocks = x[:, : (x.shape[1] // n) * n].reshape(n, n, -1)
    cases = {
        "bcast": (lambda a: world.bcast(a, 0), x,
                  np.broadcast_to(x[0], x.shape)),
        "allgather": (world.allgather, x, np.broadcast_to(x, (n,) + x.shape)),
        "reduce_scatter_block": (lambda a: world.reduce_scatter_block(a, SUM),
                                 blocks, blocks.sum(0, dtype=np.int32)),
        "alltoall": (world.alltoall, blocks, blocks.transpose(1, 0, 2)),
    }
    recs = []
    for name, (fn, host, want) in cases.items():
        out, c, r = timed(fn, staged(world, host))
        check(bits_equal(np.asarray(out), np.ascontiguousarray(want)),
              f"{name} != numpy")
        recs.append({"coll": name, "dtype": "int32", "bytes_per_rank": nbytes,
                     "compile_s": c, "run_s": r, "verdict": "exact vs numpy"})
    return recs


def nonblocking(world, nbytes: int, seed: int = 4) -> list[dict]:
    """iallreduce + wait and allreduce_init/start/wait, each
    bit-identical to the blocking call on the same staged buffer."""
    from ompi_tpu.op import SUM

    xd = staged(world, rank_data(world.size, nbytes, "float32", seed))
    blocking = world.allreduce(xd, SUM)
    recs = []
    req = world.allreduce_init(xd, SUM)
    for name, call in (("iallreduce_wait",
                        lambda: world.iallreduce(xd, SUM).wait()),
                       ("allreduce_init_start_wait",
                        lambda: req.start().wait())):
        out, c, r = timed(call)
        check(bits_equal(out, blocking), f"{name} != blocking allreduce")
        recs.append({"call": name, "dtype": "float32",
                     "bytes_per_rank": nbytes, "compile_s": c, "run_s": r,
                     "verdict": "bit-identical to blocking allreduce"})
    return recs


def host_path(world, nbytes: int, seed: int = 5) -> dict:
    """numpy in, numpy out: stage → collective → unstage."""
    import numpy as np

    from ompi_tpu.op import SUM, ordered_reduce_np

    x = rank_data(world.size, nbytes, "float32", seed)
    out, c, r = timed(world.allreduce, x, SUM)
    check(isinstance(out, np.ndarray), "host path must return numpy")
    golden = ordered_reduce_np(x, SUM)
    check(all(bits_equal(out[i], golden) for i in range(world.size)),
          "host-path allreduce not bit-exact vs ordered_reduce_np")
    return {"bytes_per_rank": nbytes, "path": "numpy in -> numpy out",
            "compile_s": c, "run_s": r,
            "verdict": "bit-exact vs ordered_reduce_np"}


# -- what exists only across chips ---------------------------------------

def world_vs_psum(world, nbytes: int, seed: int = 6) -> dict:
    """World fp32 SUM: the default path bitwise against a raw
    ``jax.shard_map(lax.psum)`` program, and the reproducible path
    bit-exact against ``ordered_reduce_np``."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.core import mca
    from ompi_tpu.mesh import AXIS
    from ompi_tpu.op import SUM, ordered_reduce_np

    raw = jax.jit(jax.shard_map(lambda v: lax.psum(v, AXIS),
                                mesh=world.mesh.mesh, in_specs=P(AXIS),
                                out_specs=P(AXIS)))
    store = mca.default_context().store
    x = rank_data(world.size, nbytes, "float32", seed)
    xd = staged(world, x)
    out, c, r = timed(world.allreduce, xd, SUM)
    ref, _, raw_r = timed(raw, xd)
    check(bits_equal(out, ref), f"allreduce {nbytes}B != raw psum")
    del out, ref
    store.set("coll_xla_reproducible", True)
    try:
        rep, rc, rr = timed(world.allreduce, xd, SUM)
    finally:
        store.set("coll_xla_reproducible", False)
    golden = ordered_reduce_np(x, SUM)
    check(all(bits_equal(rep[i], golden) for i in range(world.size)),
          f"reproducible allreduce {nbytes}B != ordered_reduce_np")
    return {"op": "SUM", "dtype": "float32", "bytes_per_rank": nbytes,
            "compile_s": c, "run_s": r, "raw_psum_run_s": raw_r,
            "reproducible_compile_s": rc, "reproducible_run_s": rr,
            "verdict": "bitwise = raw psum; reproducible bit-exact vs "
                       "ordered_reduce_np"}


PALLAS_FAMILIES = ("allreduce", "allgather", "reduce_scatter")


def pallas_ring(world, fam: str, nbytes: int, seed: int = 7) -> dict:
    """One ``pallas_ring`` family forced through its
    ``coll_xla_<fam>_algorithm`` var, bit-exact against the ``ring``
    family on the same input; the hop must be the DMA kernel."""
    from ompi_tpu.coll import pallas_kernels
    from ompi_tpu.core import mca
    from ompi_tpu.op import SUM

    mode = pallas_kernels.mode()
    check(mode == "dma", f"pallas_kernels.mode() is {mode}, not dma")
    n = world.size
    call = {"allreduce": lambda a: world.allreduce(a, SUM),
            "allgather": world.allgather,
            "reduce_scatter": lambda a: world.reduce_scatter_block(a, SUM),
            }[fam]
    x = rank_data(n, nbytes, "float32", seed)
    if fam == "reduce_scatter":
        x = x[:, : (x.shape[1] // n) * n].reshape(n, n, -1)
    xd = staged(world, x)
    store = mca.default_context().store
    var = f"coll_xla_{fam}_algorithm"
    res = {}
    for algo in ("ring", "pallas_ring"):
        store.set(var, algo)
        try:
            res[algo] = timed(call, xd)
        finally:
            store.set(var, "auto")
    check(bits_equal(res["pallas_ring"][0], res["ring"][0]),
          f"pallas_ring {fam} {nbytes}B != ring")
    return {"coll": fam, "dtype": "float32", "bytes_per_rank": nbytes,
            "pallas_mode": mode, "compile_s": res["pallas_ring"][1],
            "run_s": res["pallas_ring"][2], "ring_run_s": res["ring"][2],
            "verdict": "bit-exact vs ring"}


def split_colors(world, nbytes: int, seed: int = 8) -> dict:
    """MPI_Comm_split into colours by rank parity; each colour's int32
    allreduce exact against numpy over its members."""
    from ompi_tpu.op import SUM

    colors = [r % 2 for r in range(world.size)]
    comms = world.split(colors)
    rec = {"colors": colors, "dtype": "int32", "bytes_per_rank": nbytes}
    for c in sorted(set(colors)):
        comm = comms[colors.index(c)]
        x = rank_data(comm.size, nbytes, "int32", seed + c)
        out, ct, r = timed(comm.allreduce, staged(comm, x), SUM)
        want = x.sum(0, dtype=x.dtype)
        check(all(bits_equal(out[i], want) for i in range(comm.size)),
              f"split colour {c} allreduce != numpy")
        rec[f"color{c}"] = {"size": comm.size, "compile_s": ct, "run_s": r,
                            "devices": [str(d) for d in comm.mesh.devices]}
    rec["verdict"] = "every colour exact vs numpy"
    return rec


# -- driver --------------------------------------------------------------

def start_world(chips: int):
    """Cache first, then MPI_Init; refuse anything but ``chips`` TPUs."""
    import jax

    from ompi_tpu import compile_cache

    d = compile_cache.enable()
    print(f"compile cache: {d} holds {compile_cache.entries(d) if d else 0} "
          "entries at start", flush=True)
    import ompi_tpu.api as api

    world = api.init()
    dev = _device()
    check(dev["platform"] == "tpu", f"JAX found no TPU: {jax.devices()}")
    check(dev["count"] == chips == world.size,
          f"want {chips} chips, JAX sees {dev['count']}, world {world.size}")
    run_phase("world", lambda: {"size": world.size,
                                "devices": [str(d) for d in jax.devices()]})
    return world


def one_chip() -> None:
    run_phase("capi_osu_allreduce", capi_osu, 1)
    world = start_world(1)
    for nb in ONE_CHIP_SIZES:
        run_phase(f"allreduce_sum_fp32_{nb}B", allreduce_exact, world, nb)
    run_phase("op_dtype_matrix_1MiB",
              lambda: {"cases": op_dtype_matrix(world, MiB)})
    run_phase("collectives_int32_4MiB",
              lambda: {"cases": collectives(world, 4 * MiB)})
    run_phase("nonblocking_persistent_fp32_4MiB",
              lambda: {"cases": nonblocking(world, 4 * MiB)})
    run_phase("hostpath_allreduce_fp32_256MiB", host_path, world, 256 * MiB)


def four_chips() -> None:
    run_phase("capi_osu_allreduce_np4", capi_osu, 4)
    world = start_world(4)
    for nb in FOUR_CHIP_SIZES:
        run_phase(f"world_allreduce_sum_fp32_{nb}B", world_vs_psum, world, nb)
    for nb in PALLAS_SIZES:
        for fam in PALLAS_FAMILIES:
            run_phase(f"pallas_ring_{fam}_fp32_{nb}B", pallas_ring, world,
                      fam, nb)
    run_phase("split_2x2_allreduce_int32_4MiB", split_colors, world, 4 * MiB)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)
    try:
        from ompi_tpu.boot.tpurun import tpu_chip_count

        found = tpu_chip_count()
        check(found >= args.chips,
              f"needs {args.chips} TPU chip(s); this host has {found}")
        (four_chips if args.chips == 4 else one_chip)()
        from ompi_tpu import compile_cache

        print(f"compile cache: {compile_cache.cache_dir()} holds "
              f"{compile_cache.entries()} entries at end", flush=True)
        print(json.dumps({"ok": True, "device": _device()}), flush=True)
        return 0
    except Exception:  # noqa: BLE001 — every failure ends the run
        traceback.print_exc()
        sys.stdout.flush()
        return 1


if __name__ == "__main__":
    sys.exit(main())
