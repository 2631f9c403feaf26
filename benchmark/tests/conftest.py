"""Four virtual CPU devices, so a cell's world has four ranks here as it
has on a v5e 2x2.  Set before JAX picks a backend."""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

#: tiny stand-ins for the mixes, so a whole run fits a CPU test
TINY = {"large": [4096, 65536]}


def copy_tiny(root):
    """A copy of BENCHMARK.json and the benchmark's data and readers in
    ``root``, with every mix cut to a few small sizes."""
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, sizes in TINY.items():
        p = root / "benchmark" / "traffic" / f"{name}.json"
        mix = json.loads(p.read_text())
        mix.update(sizes_bytes=sizes, repeats_per_cycle=3, trace_seconds=0.2)
        p.write_text(json.dumps(mix))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return copy_tiny(tmp_path)


def add_alltoall(root):
    """``device_alltoall`` added to ``root`` by files alone, as a later
    change would add a deployment: the call module
    ``benchmark/calls/osu_alltoall.py`` (``fixtures/osu_alltoall.py``),
    its config (device buffers, float32, compared bit for bit), its mix
    and its entries in BENCHMARK.json."""
    shutil.copy(Path(__file__).parent / "fixtures" / "osu_alltoall.py",
                root / "benchmark" / "calls" / "osu_alltoall.py")
    cfg = {"name": "osu_alltoall_device", "benchmark": "osu_alltoall",
           "dtype": "float32", "buffers": "device", "chips": 4, "ranks": 4,
           "ranks_per_chip": 1,
           "check": {"compared": "mismatches: elements of any rank's "
                                 "result that differ bit for bit from "
                                 "the block transpose", "mismatches": 0}}
    (root / "benchmark" / "configs" / "osu_alltoall_device.json").write_text(
        json.dumps(cfg))
    (root / "benchmark" / "traffic" / "alltoall.json").write_text(json.dumps(
        {"loop": "closed", "callers": 1, "sizes_bytes": [1024, 16384, 65536],
         "repeats_per_cycle": 3, "inputs_per_size": 2,
         "checked_per_size": 1, "trace_seconds": 0.2}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(
        {"name": "osu_alltoall_device", "source": "OSU osu_alltoall -d",
         "file": "benchmark/configs/osu_alltoall_device.json",
         "reduced": [], "why": "a collective added by files alone"})
    spec["workloads"].append(
        {"name": "device_alltoall", "config": "osu_alltoall_device",
         "traffic": "alltoall", "chips": 4,
         "why": "a collective added by files alone"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("device_alltoall")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def add_hbm_to_host(root):
    """``hbm_read`` added to ``root`` by files alone, as a later change
    would add a one-chip deployment: the call module
    ``benchmark/calls/hbm_to_host.py`` (``fixtures/hbm_to_host.py``),
    its config (one rank on one chip, compared bit for bit) and its
    entries in BENCHMARK.json, on the mix ``large``."""
    shutil.copy(Path(__file__).parent / "fixtures" / "hbm_to_host.py",
                root / "benchmark" / "calls" / "hbm_to_host.py")
    cfg = {"name": "hbm_to_host_one_rank", "benchmark": "hbm_to_host",
           "dtype": "float32", "buffers": "device", "chips": 1, "ranks": 1,
           "ranks_per_chip": 1,
           "check": {"compared": "mismatches: elements of the result that "
                                 "differ bit for bit from the input",
                     "mismatches": 0}}
    (root / "benchmark" / "configs" / "hbm_to_host_one_rank.json").write_text(
        json.dumps(cfg))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(
        {"name": "hbm_to_host_one_rank", "source": "device to host copy",
         "file": "benchmark/configs/hbm_to_host_one_rank.json",
         "reduced": [], "why": "a one-rank call added by files alone"})
    spec["workloads"].append(
        {"name": "hbm_read", "config": "hbm_to_host_one_rank",
         "traffic": "large", "chips": 1,
         "why": "a one-rank call added by files alone"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("busbw_GBps", "idle_share.busbw"):
            m["workloads"].append("hbm_read")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture
def bench_root(tiny_root):
    """``tiny_root`` with two more cells added by files alone, as a later
    change would add them: ``host_large``, osu_allreduce with numpy send
    buffers on four ranks (OSU's default host-buffer mode), so every call
    stages in, reduces across the ranks and stages out; and
    ``device_alltoall`` (``add_alltoall``)."""
    cfgs = tiny_root / "benchmark" / "configs"
    host = json.loads((cfgs / "osu_allreduce_device.json").read_text())
    host.update(name="osu_allreduce_host", buffers="host")
    (cfgs / "osu_allreduce_host.json").write_text(json.dumps(host))
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append(
        {"name": "osu_allreduce_host", "source": host["source"],
         "file": "benchmark/configs/osu_allreduce_host.json",
         "reduced": [], "why": "numpy send buffers on four ranks"})
    spec["workloads"].append(
        {"name": "host_large", "config": "osu_allreduce_host",
         "traffic": "large", "chips": 4, "why": "a cell added by data alone"})
    for m in spec["end_to_end"]:
        if m["name"] == "busbw_GBps":
            m["workloads"].append("host_large")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    add_alltoall(tiny_root)
    return tiny_root
