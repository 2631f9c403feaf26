"""Build-on-demand loader for the native components (native/).

≈ the MCA dynamic-component loader (``mca_base_component_repository``,
SURVEY.md §2.1 "MCA base"): native pieces are optional shared objects
discovered/built at runtime; the convertor degrades to the pure
jax/numpy paths when the toolchain is absent.

* ``libtpumpi.so`` — the C ``mpi.h`` ABI (native/src/shim.c).
* ``libtpuconvertor.so`` — datatype pack/unpack kernels.

``compile_mpi_program`` turns a stock MPI C source into an executable
linked against libtpumpi, so OSU-style benchmarks build unmodified.
"""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import threading
from pathlib import Path

NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"
BUILD_DIR = NATIVE_DIR / "build"

_lock = threading.Lock()
_built = False
_convertor: ctypes.CDLL | None | bool = None


def toolchain_available() -> bool:
    return shutil.which("gcc") is not None and shutil.which("g++") is not None


def build(force: bool = False) -> None:
    """Run the native Makefile (cached per process).  ``force`` remakes
    every target from the sources (``make -B``): a ``native/build/``
    copied from another tree bakes that tree's package root into
    libtpumpi and its binaries.  Raises when there is no toolchain or
    the build fails."""
    global _built
    with _lock:
        if _built and not force:
            return
        if not toolchain_available():
            raise RuntimeError("no C toolchain (gcc/g++) on PATH")
        r = subprocess.run(
            ["make", "-C", str(NATIVE_DIR)] + (["-B"] if force else []),
            capture_output=True, text=True,
        )
        if r.returncode != 0:
            raise RuntimeError(f"native build failed:\n{r.stdout}\n{r.stderr}")
        _built = True


def lib_path(name: str) -> Path:
    return BUILD_DIR / f"lib{name}.so"


def load_convertor() -> ctypes.CDLL | None:
    """The pack/unpack kernel library, or None when unavailable."""
    global _convertor
    if _convertor is not None:
        return _convertor or None
    try:
        if not lib_path("tpuconvertor").exists():
            build()
        lib = ctypes.CDLL(str(lib_path("tpuconvertor")))
        I64P = ctypes.POINTER(ctypes.c_int64)
        lib.tpuconv_pack.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, I64P, I64P,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.tpuconv_unpack.argtypes = list(lib.tpuconv_pack.argtypes)
        lib.tpuconv_copy_strided.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.tpuconv_version.restype = ctypes.c_int
        _convertor = lib
        return lib
    except (OSError, RuntimeError):
        _convertor = False
        return None


def compile_mpi_program(
    source: str | Path, output: str | Path, extra_flags: list[str] | None = None
) -> Path:
    """Compile a stock MPI C program against libtpumpi.

    ≈ the reference's ``mpicc`` wrapper: adds -I for mpi.h, links
    -ltpumpi with an rpath so the binary runs without LD_LIBRARY_PATH.
    """
    build()
    out = Path(output)
    cmd = [
        "gcc", "-O2", "-Wall",
        f"-I{NATIVE_DIR / 'include'}",
        str(source), "-o", str(out),
        f"-L{BUILD_DIR}", "-ltpumpi",
        f"-Wl,-rpath,{BUILD_DIR}",
    ] + (extra_flags or [])
    r = subprocess.run(cmd, capture_output=True, text=True)
    if r.returncode != 0:
        raise RuntimeError(f"mpicc failed: {' '.join(cmd)}\n{r.stdout}\n{r.stderr}")
    return out


def mpicc_main(argv: list[str]) -> int:
    """``python -m ompi_tpu mpicc prog.c -o prog`` — the wrapper CLI."""
    if not argv:
        print("usage: ompi_tpu mpicc <source.c> [-o out] [extra gcc flags]")
        return 2
    src = argv[0]
    out = "a.out"
    extra = []
    it = iter(argv[1:])
    for a in it:
        if a == "-o":
            out = next(it, "a.out")
        else:
            extra.append(a)
    compile_mpi_program(src, out, extra)
    return 0
