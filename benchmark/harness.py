"""One run of one cell of ``BENCHMARK.json``: set up, measure, check.

Everything that belongs to a cell is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that names, the
collective that the configuration's ``benchmark`` names (``load_call``),
its traffic in ``benchmark/traffic/<traffic>.json`` and each metric in
``benchmark/metrics/<metric>.py`` (``load_reader``).  A configuration's
``buffers`` is ``device`` (OSU ``-d``: send buffers staged to the chips
once) or ``host`` (numpy in, numpy out, through the library's staging
on every call).  A cell, a collective, a mix or a metric is added by
adding files and entries, never by editing this one.

The run drives the collective on the world that ``ompi_tpu.api.init()``
returns, one blocking call after another, as the OSU benchmarks do, in
whole cycles of the mix.  ``--trace 0`` measures for ``seconds`` (to
the end of the cycle under way) and reports the cell's end-to-end
metrics; ``--trace 1`` records a profiler trace of the mix's shorter
``trace_seconds`` and reports its per-layer metrics.  Both compare
sampled results with the call module's reference after the window.

A traced run records two windows of the same schedule, each in its own
profiler session.  The first, with the library's tracing off as in the
measured window, gives the readers ``run.trace``: the device's busy
time, its operations and the ``bench.*`` host spans.  The second, with
``ompi_tpu.trace`` on, gives them ``run.lib``: that window's trace and
the library's ``ompi.*`` spans, as ``benchmark/libspans.py`` takes them.
A deployment's call module names its api span (``API_SPAN``), and its
per-layer readers read its spans from ``run.lib``: its span numbers
come by files alone too.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np

from . import arith, generate, libspans, trace as trace_mod

ROOT = Path(__file__).resolve().parent.parent
WRONG = 1e300  # a host caller handed a device array; finite for JSON


class DeviceError(RuntimeError):
    """No TPU, or not the cell's number of chips."""


# -- the cell, found by name -----------------------------------------------

def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    wl = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    reported = [m for m in spec["end_to_end"]
                if name in m.get("workloads", [name])]
    moved = {m["name"] for m in reported}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in moved
                              else [])]
    config = json.loads((root / cfg["file"]).read_text())
    call = load_call(root, config["benchmark"])
    mix = generate.load_mix(root / "benchmark" / "traffic"
                            / f"{wl['traffic']}.json")
    call.validate(mix, config)
    return SimpleNamespace(name=name, chips=wl["chips"], root=root,
                           config=config, call=call, mix=mix,
                           end_to_end=reported, per_layer=layer)


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_call(root: Path, benchmark: str):
    """``benchmark/calls/<benchmark>.py``, all that the harness and the
    readers know of one collective: ``validate(mix, cfg)``; ``inputs(cfg,
    mix, seed, n, sharding, on_host)``, as ``inputs[size index][slot]``;
    ``bind(world, cfg)``, the call the window times; ``error(x, out,
    cfg)``, the number held to ``cfg["check"]``'s limit; ``bus_bytes(
    nbytes, n)`` and ``floor_s(nbytes, n, peaks)``, ``None`` where it has
    no such model; ``DEVICE_OPS``, the opcode prefix of its operations on
    the chips; ``control(x)``, the stand-in of ``control.py``; and, where
    the library spans its call, ``API_SPAN``, the name of that span."""
    path = root / "benchmark" / "calls" / f"{benchmark}.py"
    if not path.exists():
        raise FileNotFoundError(f"no call module {path} for {benchmark!r}")
    return _module(path, f"_bench_call_{benchmark}")


def load_reader(root: Path, metric: str):
    """``benchmark/metrics/<metric>.py``'s ``read(run)``, else that of
    ``<name before the first '.'>.py``: a quantity split by the
    end-to-end metric it moves (``idle_share.busbw``) shares one reader."""
    path = root / "benchmark" / "metrics" / f"{metric}.py"
    if not path.exists():
        path = path.with_name(f"{metric.split('.', 1)[0]}.py")
    return _module(path, f"_bench_metric_{metric.replace('.', '_')}").read


# -- the device ---------------------------------------------------------------

def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> None:
    """Refuse anything but a TPU host with exactly ``chips`` chips."""
    dev = device_info()
    if dev["platform"] != "tpu":
        raise DeviceError(f"JAX found no TPU: platform {dev['platform']}")
    if dev["count"] != chips:
        raise DeviceError(f"cell needs {chips} chip(s); JAX sees {dev['count']}")


def memory_peak_bytes() -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


# -- compiles inside the window ------------------------------------------------

class CompileCounter:
    """Counts JAX's compile events (tracing, lowering, backend compile
    or cache load) inside a ``with`` block."""

    def __init__(self):
        self.count = 0

    def _event(self, name, _secs, **_kw):
        if name.startswith("/jax/core/compile/"):
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._event)


# -- the window ------------------------------------------------------------------

def window(call, inputs, sched, keep, seconds: float, annotate: bool):
    """Back-to-back blocking calls along ``sched``, cycle after cycle,
    until the first cycle that ends past ``seconds``: whole cycles, so
    every seed's window holds the same mix.  Returns (size index per
    call, latency per call, window seconds, {position: (size, slot,
    result)}) with the latest result of each position in ``keep``."""
    if annotate:
        from jax.profiler import TraceAnnotation
    sizes, lats, kept = [], [], {}
    L, i = len(sched), 0
    t_start = time.perf_counter()
    deadline = t_start + seconds
    while True:
        pos = i % L
        si, slot = sched[pos]
        x = inputs[si][slot]
        t0 = time.perf_counter()
        if annotate:
            with TraceAnnotation("bench.call"):
                out = call(x)
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready(out)
        else:
            out = call(x)
            jax.block_until_ready(out)
        t1 = time.perf_counter()
        sizes.append(si)
        lats.append(t1 - t0)
        if pos in keep:
            kept[pos] = (si, slot, out)
        i += 1
        if t1 >= deadline and i % L == 0:
            return sizes, lats, t1 - t_start, kept


def _start_trace(trace_dir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def library_window(call, inputs, sched, keep,
                   seconds: float) -> tuple[str, int]:
    """The window again, annotated, in a profiler session of its own,
    with the library's tracing on, so that its ``ompi.*`` spans land in
    the trace.  It holds the results of ``keep`` as the measured window
    does (a held result is no spare for the library to reuse) and drops
    them after.  Returns the trace's directory and the compiles inside
    the window."""
    from ompi_tpu import trace as lib_trace

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_lib_")
    lib_trace.enable(True)
    try:
        _start_trace(trace_dir)
        with CompileCounter() as compiles, \
                jax.profiler.TraceAnnotation("bench.window"):
            window(call, inputs, sched, keep, seconds, True)
        jax.profiler.stop_trace()
    finally:
        lib_trace.enable(False)
    return trace_dir, compiles.count


def _read_trace(trace_dir: str, *reducers) -> tuple:
    """Each of ``reducers`` applied to the profile that the session wrote
    under ``trace_dir``, which is then deleted."""
    pd = trace_mod.profile(trace_dir)
    out = tuple(f(pd) for f in reducers)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def check(call, cfg: dict, kept, sizes_compared: int, n_sizes: int,
          want_host: bool) -> tuple[dict, int, bool]:
    """Compare each kept (input, result, result was numpy) with the call
    module's reference, under the one limit that ``cfg["check"]`` gives
    beside its ``compared``; every size of the mix must have been
    compared.  Returns the numbers compared, each beside its limit, how
    many results failed, and whether the run is correct."""
    [(name, limit)] = [kv for kv in cfg["check"].items()
                       if kv[0] != "compared"]
    errs = [WRONG if want_host and not was_numpy
            else call.error(x, np.asarray(out), cfg)
            for x, out, was_numpy in kept]  # host callers are owed numpy
    worst = max(errs, default=0.0)
    return ({name: {"value": worst, "limit": limit},
             "sizes_compared": {"value": sizes_compared, "limit": n_sizes}},
            sum(e > limit for e in errs),
            worst <= limit and sizes_compared >= n_sizes)


# -- one run ---------------------------------------------------------------------

def run(cell, seed: int, seconds: float, traced: bool, t_process: float,
        call=None, chip_check: bool = True) -> dict:
    """Set up, measure, check; returns the result line's object.
    ``call`` replaces the call module's bound call (the control and the
    fault tests use it); ``chip_check=False`` skips the device check."""
    if chip_check:
        require_chips(cell.chips)
    import ompi_tpu.api as api

    world = api.init()
    dev = device_info()
    n = world.size
    cfg, mix = cell.config, cell.mix
    if call is None:
        call = cell.call.bind(world, cfg)
    on_host = cfg["buffers"] == "host"
    inputs = cell.call.inputs(cfg, mix, seed, n, world.mesh.rank_sharding(),
                              on_host)
    sched = generate.cycle(mix, seed)
    keep = set(generate.checked_positions(mix, sched, seed))
    for slots in inputs:  # warm every shape of this mix, and only those
        for x in slots:
            for _ in range(2):
                jax.block_until_ready(call(x))
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        _start_trace(trace_dir)
        seconds = min(seconds, mix["trace_seconds"])
    setup_s = time.perf_counter() - t_process
    span = (jax.profiler.TraceAnnotation("bench.window") if traced
            else contextlib.nullcontext())
    with CompileCounter() as compiles, span:
        sizes, lats, wall, kept = window(call, inputs, sched, keep, seconds,
                                         traced)
    if traced:
        jax.profiler.stop_trace()
    t_check = time.perf_counter()
    # one device_get for every array, so their copies overlap
    host_kept = jax.device_get(
        [(inputs[si][sl], o, isinstance(o, np.ndarray))
         for si, sl, o in kept.values()])
    sizes_compared = len({si for si, _, _ in kept.values()})
    t_check = time.perf_counter() - t_check
    del kept  # the results are on the host: the device holds them no more
    if traced:
        lib_dir, lib_compiles = library_window(call, inputs, sched, keep,
                                               seconds)
    del inputs
    mem = memory_peak_bytes()
    t0 = time.perf_counter()
    numbers, failed, correct = check(cell.call, cfg, host_kept,
                                     sizes_compared, len(mix["sizes_bytes"]),
                                     on_host)
    del host_kept
    t_check += time.perf_counter() - t0
    ctx = SimpleNamespace(
        n=n, sizes_bytes=mix["sizes_bytes"], calls=sizes, lat_s=lats,
        window_s=wall, setup_s=setup_s, device_kind=dev["kind"],
        ici_links=arith.ici_links([getattr(d, "coords", None)
                                   for d in jax.devices()]),
        trace=None, lib=None, call=cell.call)
    device = {**dev, "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": len(sizes), "failed": failed}
    if traced:
        [ctx.trace] = _read_trace(trace_dir, trace_mod.from_profile)
        ctx.lib = _read_trace(lib_dir, trace_mod.from_profile,
                             libspans.from_profile)
        device["busy_s"] = ctx.trace.busy_s()
        device["window_s"] = ctx.trace.window_s()
        result["breakdown"] = ctx.trace.breakdown()
        metrics = cell.per_layer
    else:
        metrics = cell.end_to_end
    values = {}
    for m in metrics:
        v = load_reader(cell.root, m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    print(f"compiles_in_window {compiles.count}", file=sys.stderr)
    if traced:
        print(f"compiles_in_library_window {lib_compiles}", file=sys.stderr)
    print(f"check_seconds {t_check}", file=sys.stderr, flush=True)
    result.update(metrics=values, device=device, check=numbers)
    return result
