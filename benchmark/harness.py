"""One run of one cell of ``BENCHMARK.json``: set up, measure, check.

Everything that belongs to a cell is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that names, its
traffic in ``benchmark/traffic/<traffic>.json`` and each metric in
``benchmark/metrics/<metric>.py`` (see ``load_reader``).  A
configuration's ``buffers`` is ``device`` (OSU ``-d``: send buffers
staged to the chips once) or ``host`` (numpy in, numpy out, through
the library's staging on every call).  A cell, a mix or a metric is added
by adding files and entries, never by editing this one.

The run drives ``Comm.allreduce`` on the world that
``ompi_tpu.api.init()`` returns, one blocking call after another, as
``osu_allreduce`` does, in whole cycles of the mix.  ``--trace 0``
measures for ``seconds`` (to the end of the cycle under way) and
reports the cell's end-to-end metrics; ``--trace 1`` records a
profiler trace of the mix's shorter ``trace_seconds`` and reports its
per-layer metrics.  Both compare sampled results with ``reference``
after the window.
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

from . import generate, reference, trace as trace_mod

ROOT = Path(__file__).resolve().parent.parent


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
    return SimpleNamespace(
        name=name, chips=wl["chips"], root=root,
        config=json.loads((root / cfg["file"]).read_text()),
        mix=generate.load_mix(root / "benchmark" / "traffic"
                              / f"{wl['traffic']}.json"),
        end_to_end=reported, per_layer=layer)


def load_reader(root: Path, metric: str):
    """``benchmark/metrics/<metric>.py``'s ``read(run)``, else that of
    ``<name before the first '.'>.py``: a quantity split by the
    end-to-end metric it moves (``idle_share.busbw``) shares one reader."""
    path = root / "benchmark" / "metrics" / f"{metric}.py"
    if not path.exists():
        path = path.with_name(f"{metric.split('.', 1)[0]}.py")
    spec = importlib.util.spec_from_file_location(
        f"_bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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


# -- inputs made from the seed ----------------------------------------------

def _normals(key_data, shapes):
    keys = jax.random.split(jax.random.wrap_key_data(key_data), len(shapes))
    return tuple(jax.random.normal(k, s, np.float32)
                 for k, s in zip(keys, shapes))


def make_inputs(mix: dict, seed: int, n: int, sharding, on_host: bool):
    """``inputs[size index][slot]``: standard normal float32 rank-major
    (n, count) buffers made on the device in one jitted call from
    ``seed``; host buffers are copied to numpy before the window."""
    k = mix["inputs_per_size"]
    shapes = tuple((n, s // 4) for s in mix["sizes_bytes"] for _ in range(k))
    key = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    make = jax.jit(_normals, static_argnums=1,
                   out_shardings=(sharding,) * len(shapes))
    flat = list(make(key, shapes))
    jax.block_until_ready(flat)
    if on_host:
        for i, a in enumerate(flat):
            flat[i] = np.asarray(a)
            a.delete()
    return [flat[i * k:(i + 1) * k] for i in range(len(mix["sizes_bytes"]))]


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


def check(kept, sizes_compared: int, n_sizes: int, limit: float,
          want_host: bool) -> tuple[dict, int, bool]:
    """Compare each kept (input, result, result was numpy) with the
    reference; every size of the mix must have been compared.  Returns
    the numbers compared, each beside its limit, how many results failed,
    and whether the run is correct."""
    errs = [reference.WRONG if want_host and not was_numpy
            else reference.max_err_eps(x, np.asarray(out))
            for x, out, was_numpy in kept]  # host callers are owed numpy
    worst = max(errs, default=0.0)
    return ({"max_err_eps": {"value": worst, "limit": limit},
             "sizes_compared": {"value": sizes_compared, "limit": n_sizes}},
            sum(e > limit for e in errs),
            worst <= limit and sizes_compared >= n_sizes)


# -- one run ---------------------------------------------------------------------

def run(cell, seed: int, seconds: float, traced: bool, t_process: float,
        call=None, chip_check: bool = True) -> dict:
    """Set up, measure, check; returns the result line's object.
    ``call`` replaces ``world.allreduce(x, op)`` (the control and the
    fault tests use it); ``chip_check=False`` skips the device check."""
    if chip_check:
        require_chips(cell.chips)
    import ompi_tpu.api as api
    from ompi_tpu import op as ops

    world = api.init()
    dev = device_info()
    n = world.size
    cfg, mix = cell.config, cell.mix
    if call is None:
        op = getattr(ops, cfg["op"])
        call = lambda x: world.allreduce(x, op)  # noqa: E731
    on_host = cfg["buffers"] == "host"
    inputs = make_inputs(mix, seed, n, world.mesh.rank_sharding(), on_host)
    sched = generate.cycle(mix, seed)
    keep = set(generate.checked_positions(mix, sched, seed))
    for slots in inputs:  # warm every shape of this mix, and only those
        for x in slots:
            for _ in range(2):
                jax.block_until_ready(call(x))
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        seconds = min(seconds, mix["trace_seconds"])
    setup_s = time.perf_counter() - t_process
    span = (jax.profiler.TraceAnnotation("bench.window") if traced
            else contextlib.nullcontext())
    with CompileCounter() as compiles, span:
        sizes, lats, wall, kept = window(call, inputs, sched, keep, seconds,
                                         traced)
    if traced:
        jax.profiler.stop_trace()
    mem = memory_peak_bytes()
    t_check = time.perf_counter()
    # one device_get for every array, so their copies overlap
    host_kept = jax.device_get(
        [(inputs[si][sl], o, isinstance(o, np.ndarray))
         for si, sl, o in kept.values()])
    sizes_compared = len({si for si, _, _ in kept.values()})
    del inputs, kept
    numbers, failed, correct = check(host_kept, sizes_compared,
                                     len(mix["sizes_bytes"]),
                                     cfg["check"]["max_err_eps"], on_host)
    del host_kept
    t_check = time.perf_counter() - t_check
    ctx = SimpleNamespace(
        n=n, sizes_bytes=mix["sizes_bytes"], calls=sizes, lat_s=lats,
        window_s=wall, setup_s=setup_s, device_kind=dev["kind"], trace=None)
    device = {**dev, "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": len(sizes), "failed": failed}
    if traced:
        ctx.trace = trace_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
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
    print(f"check_seconds {t_check}", file=sys.stderr, flush=True)
    result.update(metrics=values, device=device, check=numbers)
    return result
