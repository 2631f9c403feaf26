"""Whole runs of every cell at tiny sizes on four virtual CPU devices,
past the harness's look for a chip: the data-driven lookup, the window,
the comparison with the reference, and that a broken path or the
lower-precision control comes out not correct.  Besides the committed
cells, ``host_large`` (added by data alone, see ``conftest.bench_root``)
drives the host-buffer path at four ranks, and ``device_alltoall``
(added by files alone, see ``conftest.add_alltoall``) another
collective through its own call module.  ``hbm_read`` (added by files
alone, see ``conftest.add_hbm_to_host``) runs on one rank, in a process
with one CPU device."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import harness, libspans

from conftest import add_hbm_to_host, copy_tiny

ROOT = harness.ROOT
ALLREDUCE = [w["name"] for w in
             json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
ALLREDUCE += ["host_large"]
CELLS = ALLREDUCE + ["device_alltoall"]
SEED = 2**31 + 4242


def run_cell(root, cell, traced=False, call=None, seed=SEED):
    c = harness.load_cell(cell, root)
    return c, harness.run(c, seed, 0.3, traced, time.perf_counter(),
                          call=call, chip_check=False)


@pytest.mark.parametrize("traced", [False, True], ids=["measure", "trace"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_whole_run_is_correct(bench_root, cell, traced):
    c, res = run_cell(bench_root, cell, traced)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert res["device"]["count"] == 4
    if traced:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        # no device planes on the CPU: only host-span readers report
        assert set(res["metrics"]) <= {m["name"] for m in c.per_layer}
    else:
        assert set(res["metrics"]) == {m["name"] for m in c.end_to_end}
        assert {"busbw_GBps", "setup_s"} <= set(res["metrics"])


def _world_call():
    import ompi_tpu.api as api
    from ompi_tpu.op import SUM

    world = api.init()
    return lambda x: world.allreduce(x, SUM)


def _no_exchange(x):
    return x


def _half_left_out(x):
    out = _world_call()(x)
    half = x.shape[1] // 2
    if isinstance(out, np.ndarray):
        out = out.copy()
        out[:, half:] = x[:, half:]
        return out
    return out.at[:, half:].set(x[:, half:])


def _device_array_to_a_host_caller(x):
    import jax

    out = _world_call()(x)
    return jax.device_put(out) if isinstance(out, np.ndarray) else out


def test_a_host_caller_is_owed_numpy(bench_root):
    _, res = run_cell(bench_root, "host_large",
                      call=_device_array_to_a_host_caller)
    assert not res["correct"] and res["failed"] > 0


def _answer_altered(x):
    out = _world_call()(x)
    if isinstance(out, np.ndarray):
        out = out.copy()
        out[-1, -1] += 1
        return out
    return out.at[-1, -1].add(1)


def _bf16_control(x):
    return harness.load_call(ROOT, "osu_allreduce").control(x)


@pytest.mark.parametrize("fault", [_no_exchange, _half_left_out,
                                   _answer_altered, _bf16_control],
                         ids=["no_exchange", "half_left_out",
                              "answer_altered", "bf16_control"])
@pytest.mark.parametrize("cell", ALLREDUCE)
def test_a_broken_path_is_not_correct(bench_root, cell, fault):
    _, res = run_cell(bench_root, cell, call=fault)
    assert not res["correct"], res["check"]
    assert res["check"]["max_err_eps"]["value"] > \
        res["check"]["max_err_eps"]["limit"]


def _own_block_only(x):
    """The exchange left out: each rank keeps the block it sends itself,
    and receives nothing in the others."""
    import jax.numpy as jnp

    return jnp.eye(x.shape[0], dtype=x.dtype)[:, :, None] * x


def _alltoall_altered(x):
    import ompi_tpu.api as api

    return api.init().alltoall(x).at[-1, 0, -1].add(1)


@pytest.mark.parametrize("fault", ["no_exchange", "in_place",
                                   "answer_altered", "bf16_control"])
def test_a_broken_alltoall_is_not_correct(bench_root, fault):
    """Three broken paths and the control of the collective added by
    files alone: each reads mismatches above its limit of 0."""
    ctl = harness.load_call(bench_root, "osu_alltoall").control
    call = {"no_exchange": _own_block_only, "in_place": lambda x: x,
            "answer_altered": _alltoall_altered, "bf16_control": ctl}[fault]
    _, res = run_cell(bench_root, "device_alltoall", call=call)
    assert not res["correct"], res["check"]
    assert res["check"]["mismatches"]["value"] > 0
    assert res["check"]["mismatches"]["limit"] == 0


def test_a_new_cell_is_found_with_no_code_edit(bench_root):
    """``host_large`` came as a config file and BENCHMARK.json entries;
    a new mix comes as one more file."""
    (bench_root / "benchmark" / "traffic" / "mid.json").write_text(json.dumps(
        {"loop": "closed", "callers": 1, "sizes_bytes": [256, 8192],
         "repeats_per_cycle": 2, "inputs_per_size": 1,
         "checked_per_size": 1, "trace_seconds": 0.2}))
    spec = json.loads((bench_root / "BENCHMARK.json").read_text())
    spec["workloads"].append(
        {"name": "host_mid", "config": "osu_allreduce_host",
         "traffic": "mid", "chips": 4, "why": "a cell added by data alone"})
    for m in spec["end_to_end"]:
        if m["name"] == "busbw_GBps":
            m["workloads"].append("host_mid")
    (bench_root / "BENCHMARK.json").write_text(json.dumps(spec))
    c, res = run_cell(bench_root, "host_mid")
    assert c.mix["sizes_bytes"] == [256, 8192]
    assert c.config["buffers"] == "host" and c.chips == 4
    assert res["correct"] and res["check"]["sizes_compared"]["value"] == 2
    assert set(res["metrics"]) == {"busbw_GBps", "setup_s"}


def test_a_split_metric_falls_back_to_its_quantity_s_reader():
    read = harness.load_reader(ROOT, "idle_share.some_later_target")
    assert read is not None
    with pytest.raises(FileNotFoundError):
        harness.load_reader(ROOT, "no_such_quantity.busbw")


def test_an_unknown_cell_is_refused(tiny_root):
    with pytest.raises(KeyError):
        harness.load_cell("no_such_cell", tiny_root)


def test_a_config_that_names_no_call_module_is_refused(tiny_root):
    cfg = tiny_root / "benchmark" / "configs" / "osu_allreduce_device.json"
    cfg.write_text(json.dumps({**json.loads(cfg.read_text()),
                               "benchmark": "osu_nothing"}))
    with pytest.raises(FileNotFoundError) as err:
        harness.load_cell("device_large", tiny_root)
    assert str(tiny_root / "benchmark" / "calls" / "osu_nothing.py") in \
        str(err.value)


def test_the_command_refuses_a_host_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "device_large",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())


SPAN_READERS = {"api_self_us.busbw", "launch_us.busbw",
                "recycle_hit_share.busbw"}


def test_a_traced_run_reads_the_library_spans_in_a_second_window(
        tiny_root, monkeypatch):
    """The first traced window runs with the library's tracing off and
    holds no ``ompi.*`` event; the second, in its own profiler session,
    holds the call module's api spans, which the span readers read.  On
    the CPU there are no device planes: ``ici_roofline`` and
    ``idle_share.busbw`` report nothing, as before."""
    from ompi_tpu import trace as lib_trace

    profiles = []
    profile = harness.trace_mod.profile

    def keep(trace_dir):
        profiles.append(profile(trace_dir))
        return profiles[-1]

    monkeypatch.setattr(harness.trace_mod, "profile", keep)
    c, res = run_cell(tiny_root, "device_large", traced=True)
    assert res["correct"], res["check"]
    assert not lib_trace.enabled()
    first, second = (libspans.from_profile(pd) for pd in profiles)
    assert first == {}
    api = c.call.API_SPAN
    assert second[api] and second[libspans.LAUNCH]
    assert set(res["metrics"]) == SPAN_READERS
    assert {m["name"] for m in c.per_layer} == SPAN_READERS | {
        "ici_roofline", "idle_share.busbw", "runtime_launch_us.busbw",
        "runtime_complete_us.busbw"}
    tr = harness.trace_mod.from_profile(profiles[1])
    in_window = [s for s, e, _ in second[api] if tr.lo <= s and e <= tr.hi]
    assert len(in_window) == len(tr.spans["bench.call"]) > 0
    assert res["metrics"]["recycle_hit_share.busbw"]["value"] == \
        libspans.recycle_hit_share(tr, second, api)


ONE_DEVICE = """
import json, sys, time
from pathlib import Path

sys.path.insert(0, sys.argv[2])
from benchmark import harness

root, seed = Path(sys.argv[1]), int(sys.argv[3])


def altered(x):
    out = harness.load_call(root, "hbm_to_host").bind(None, {})(x).copy()
    out[-1, -1] += 1
    return out


got = {}
for name, cell, call, traced in [("hbm_read", "hbm_read", None, False),
                                 ("hbm_read_traced", "hbm_read", None, True),
                                 ("altered", "hbm_read", altered, False),
                                 ("allreduce", "device_large", None, False)]:
    c = harness.load_cell(cell, root)
    got[name] = harness.run(c, seed, 0.3, traced, time.perf_counter(),
                            call=call, chip_check=False)
print(json.dumps(got))
"""


@pytest.fixture(scope="module")
def one_device(tmp_path_factory):
    """``hbm_read`` and ``device_large`` in a process with one CPU
    device: one rank."""
    root = copy_tiny(tmp_path_factory.mktemp("one_device"))
    add_hbm_to_host(root)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    r = subprocess.run([sys.executable, "-c", ONE_DEVICE, str(root),
                        str(ROOT), str(SEED)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.splitlines()[-1])


def test_a_one_rank_call_reports_busbw_and_is_correct(one_device):
    res = one_device["hbm_read"]
    assert res["device"]["count"] == 1
    assert res["correct"] and res["failed"] == 0, res["check"]
    assert res["check"]["mismatches"] == {"value": 0.0, "limit": 0}
    assert set(res["metrics"]) == {"busbw_GBps", "setup_s"}
    assert res["metrics"]["busbw_GBps"]["value"] > 0


def test_a_one_rank_call_without_an_api_span_gets_no_span_numbers(
        one_device):
    res = one_device["hbm_read_traced"]
    assert res["correct"], res["check"]
    assert not set(res["metrics"]) & SPAN_READERS


def test_a_one_rank_answer_altered_is_not_correct(one_device):
    res = one_device["altered"]
    assert not res["correct"] and res["failed"] > 0
    assert res["check"]["mismatches"]["value"] > 0


def test_an_allreduce_on_one_rank_reports_no_busbw(one_device):
    res = one_device["allreduce"]
    assert res["device"]["count"] == 1 and res["correct"], res["check"]
    assert set(res["metrics"]) == {"setup_s"}
