"""Where JAX keeps its persistent compilation cache.

A process that finds a program in the cache loads it instead of
compiling it again, so a second run of the same job starts faster.
A cache only hits where it was written, so it lives at one fixed place:

* ``JAX_COMPILATION_CACHE_DIR``, when the environment sets it (JAX
  reads the variable itself; nothing else is set in code);
* otherwise ``<checkout>/.jax_cache`` — never a path built from a
  temporary name, a pid or the time, which would never hit — except
  in a CPU-only process (``JAX_PLATFORMS=cpu``), which keeps JAX's
  default of no cache.

``api.init()`` calls :func:`enable` before its first compile.  Programs
over some but not all of a host's TPU chips bypass the cache (the
libtpu fault this works around is described below).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the fallback: a fixed directory inside the checkout (gitignored)
DEFAULT_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def cache_dir() -> Path:
    """The directory the cache lives in: the environment's, else the
    checkout's fixed one."""
    env = os.environ.get(ENV)
    return Path(env) if env else DEFAULT_DIR


def enable() -> Path | None:
    """Point JAX at :func:`cache_dir` and cache every program, however
    fast it compiled (JAX's default skips compiles under a second,
    which is nearly every collective here), except sub-slice programs
    (:func:`skips_cache`).  Returns the directory, or
    None for a CPU-only process with no directory set: tests and
    virtual-device runs keep JAX's default of no cache."""
    import jax

    d = cache_dir()
    if ENV not in os.environ:
        if jax.config.jax_platforms == "cpu":
            return None
        jax.config.update("jax_compilation_cache_dir", str(d))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _guard_sub_slices()
    return d


def entries(d: Path | None = None) -> int:
    """How many compiled programs the cache directory holds."""
    d = cache_dir() if d is None else Path(d)
    return sum(1 for _ in d.glob("*-cache")) if d.is_dir() else 0


# -- programs that must not be loaded from the cache ---------------------
#
# libtpu 0.0.34 (JAX 0.9) halts a core when it launches an executable
# DESERIALIZED from the persistent cache for a proper subset of the
# host's chips that leaves out chip 0: the {TPU_1, TPU_3} colour of a
# 2x2 Comm.split halts TPU_3 with "Invalid logical z: enhanced-barrier-
# parent-phase-1 no HLO mapping" and the program never completes (PR 22,
# reproduced on a v5e 2x2).  It is not the collective: indexing that
# colour's result (a small gather JAX compiles over the same two chips,
# outside this library) halts the same way, so the guard covers every
# program of the process, not only ours.  JAX's key is not at fault
# either: it hashes the device assignment, and the warm process loads
# that program's own entry.  The same HLO compiled in the running
# process works; cached programs over every chip, over {TPU_0, TPU_2}
# and over one chip load and run.  So a program over several chips but
# not all of them gets no cache key, which is how JAX itself runs a
# program with the cache off: JAX's own compile path (module dumps,
# PGLE) compiles it in-process, and nothing is read for it or written.
# {TPU_0, TPU_2} loaded fine, but one passing pair is too little to
# draw the line at chip 0: a miss costs a compile, a wrong guess hangs
# the chip.  Every other program keeps the cache.


def skips_cache(devices) -> bool:
    """True for a TPU program over several chips but not all of them."""
    import jax

    return (len(devices) > 1 and devices[0].platform == "tpu"
            and len(devices) < len(jax.devices()))


def _guard_sub_slices() -> None:
    """Withhold the cache key of every :func:`skips_cache` program.  JAX
    0.9 asks ``compiler._get_cache_key`` for the key of every program it
    compiles, and reads or writes the cache only under a key, so the
    guard wraps that function once per process."""
    from jax._src import compiler

    get_key = compiler._get_cache_key
    if getattr(get_key, "sub_slice_guard", False):
        return

    def _get_cache_key(options, backend, computation, devices, *a, **kw):
        if skips_cache(list(devices.flat)):
            return None
        return get_key(options, backend, computation, devices, *a, **kw)

    _get_cache_key.sub_slice_guard = True
    compiler._get_cache_key = _get_cache_key
