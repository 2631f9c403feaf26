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


@pytest.fixture
def tiny_root(tmp_path):
    """A copy of BENCHMARK.json and the benchmark's data and readers,
    with every mix cut to a few small sizes."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, sizes in TINY.items():
        p = tmp_path / "benchmark" / "traffic" / f"{name}.json"
        mix = json.loads(p.read_text())
        mix.update(sizes_bytes=sizes, repeats_per_cycle=3, trace_seconds=0.2)
        p.write_text(json.dumps(mix))
    return tmp_path


@pytest.fixture
def bench_root(tiny_root):
    """``tiny_root`` with one more cell added by data alone, as a later
    change would add it: ``host_large``, osu_allreduce with numpy send
    buffers on four ranks (OSU's default host-buffer mode), so every call
    stages in, reduces across the ranks and stages out."""
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
    return tiny_root
