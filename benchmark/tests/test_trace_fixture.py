"""The trace reduction on profiler traces recorded by the benchmark on
TPU v5e chips (``fixtures/``, gzipped ``.xplane.pb``): the planes it
keys on, the copies and the all-reduce it finds, and the numbers the
chip runs printed from the same traces."""

import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import arith, trace
from benchmark.harness import ROOT, load_call, load_reader

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def load(name: str) -> trace.Trace:
    import jax

    raw = gzip.decompress((FIXTURES / name).read_bytes())
    return trace.from_profile(jax.profiler.ProfileData.from_serialized_xspace(raw))


@pytest.fixture(scope="module")
def host_small():
    """One cycle (128 calls, 4 B-64 KiB) of numpy buffers through one
    rank on one chip: every call stages in and out."""
    return load("host_small_v5e_1chip.xplane.pb.gz")


def test_host_path_copies_are_found_on_the_host_plane(host_small):
    assert list(host_small.devices) == [0]
    assert len(host_small.spans["bench.call"]) == 128
    kinds = [d for _, _, d, _ in host_small.transfers]
    assert kinds.count("h2d") == 128 and kinds.count("d2h") == 128
    # one rank: the donated copy program runs no operation on the chip
    assert {trace.opcode(n) for _, _, n in host_small.devices[0]} == {"h2d", "d2h"}


def test_host_path_copies_count_as_busy(host_small):
    per_copy = [e - s for s, e, _, _ in host_small.transfers]
    assert all(t > 0 for t in per_copy)
    assert sum(per_copy) / 1e9 == pytest.approx(host_small.busy_s(), rel=0.01)
    assert host_small.window_s() == pytest.approx(0.185507154)
    assert host_small.busy_s() == pytest.approx(0.051282095)
    assert host_small.idle_share() * 100 == pytest.approx(72.35573189808086)
    idle = dict(host_small.breakdown()["idle_gaps"])
    assert idle["bench.call"] == pytest.approx(0.131995639)


@pytest.fixture(scope="module")
def device_large():
    """One cycle (10 calls, 2 of each of 1-256 MiB per rank) of
    device_large on a v5e 2x2."""
    return load("device_large_v5e_2x2.xplane.pb.gz")


def test_four_chip_planes_each_run_one_all_reduce_per_call(device_large):
    assert list(device_large.devices) == [0, 1, 2, 3]
    assert len(device_large.spans["bench.call"]) == 10
    assert device_large.transfers == []
    for ops in device_large.devices.values():
        assert [trace.opcode(n) for _, _, n in ops] == ["all-reduce"] * 10


def _run(device_large, call, ici_links=2):
    sizes = [1 << 20, 4 << 20, 16 << 20, 64 << 20, 256 << 20]
    return SimpleNamespace(trace=device_large, n=4, sizes_bytes=sizes,
                           calls=[i for i in range(5) for _ in range(2)],
                           window_s=device_large.window_s(),
                           device_kind="TPU v5 lite", ici_links=ici_links,
                           call=call)


def test_ici_roofline_arithmetic_matches_the_chip_run(device_large):
    """Over the two links that a 2x2 wires to each chip, 100 GB/s."""
    run = _run(device_large, load_call(ROOT, "osu_allreduce"))
    roof = load_reader(ROOT, "ici_roofline")(run)
    assert roof == pytest.approx(85.42096702168112)
    assert 0 < roof <= 100
    # the 256 MiB call is bound by the ICI, not by HBM
    assert arith.allreduce_floor_s(
        256 << 20, 4, arith.peaks("TPU v5 lite", 2))[1] == "ici"
    assert load_reader(ROOT, "idle_share.busbw")(run) == pytest.approx(
        47.02417412997405)
    assert device_large.busy_s() == pytest.approx(0.01255772775)


@pytest.mark.parametrize("metric", ["ici_roofline", "busbw_GBps"])
def test_a_call_with_no_model_reports_nothing(device_large, metric):
    """A call module whose ``floor_s`` and ``bus_bytes`` give None, on a
    trace that holds its operations: the readers leave the metric out
    and never read 0."""
    call = SimpleNamespace(DEVICE_OPS="all-reduce",
                           floor_s=lambda nbytes, n, pk: None,
                           bus_bytes=lambda nbytes, n: None)
    assert load_reader(ROOT, metric)(_run(device_large, call)) is None


def test_chips_with_no_coords_get_no_ici_roofline(device_large):
    run = _run(device_large, load_call(ROOT, "osu_allreduce"), ici_links=None)
    assert load_reader(ROOT, "ici_roofline")(run) is None


@pytest.fixture(scope="module")
def device_large_lib():
    """Forty calls of device_large on a v5e 2x2, the library's tracing
    on."""
    return load("device_large_lib_v5e_2x2.xplane.pb.gz")


@pytest.mark.parametrize("fixture, launch, complete", [
    ("device_large", 476.56, 300.31),
    ("device_large_lib", 334.72, 312.2955)])
def test_the_runtime_s_launch_and_completion_per_call(
        request, fixture, launch, complete):
    """Each call's runtime events on the host plane: PJRT's call into
    the program, then one launch thread per chip, then each chip's
    completion thread reading its flag and running the callbacks."""
    tr = request.getfixturevalue(fixture)
    calls = tr.runtime_calls()
    assert len(calls) == len(tr.spans["bench.call"])
    for c in calls:
        assert len(c[trace.EXECUTE]) == 1
        assert len(c[trace.LAUNCH]) == len(c[trace.SYNC]) == 4
        assert len(c[trace.COMPLETE]) == 4
    run = SimpleNamespace(trace=tr)
    got = [load_reader(ROOT, f"{m}.busbw")(run)
           for m in ("runtime_launch_us", "runtime_complete_us")]
    assert got == pytest.approx([launch, complete])
    # both lie inside the round trip, whose rest is the harness, the
    # library, the jit dispatch, the gap between the two and the return
    # to the caller
    round_trip = [we - cs for (cs, _), (_, we)
                  in zip(tr.spans["bench.call"], tr.spans["bench.wait"])]
    assert sum(got) < min(round_trip) / 1e3


def test_a_trace_with_no_runtime_events_reports_nothing(device_large):
    bare = trace.Trace(device_large.devices, device_large.spans)
    assert bare.runtime == {}
    assert all(c[trace.EXECUTE] == [] for c in bare.runtime_calls())
    run = SimpleNamespace(trace=bare)
    for m in ("runtime_launch_us.busbw", "runtime_complete_us.busbw"):
        assert load_reader(ROOT, m)(run) is None
