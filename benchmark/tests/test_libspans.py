"""The library's spans on the profiler's clock, reduced beside the
benchmark's trace: the per-call numbers, the readers over them and the
idle time by the innermost span, on synthetic traces and chip-recorded
fixtures."""

import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import libspans, trace
from benchmark.harness import ROOT, load_call, load_reader

FIXTURES = Path(__file__).resolve().parent / "fixtures"
API = load_call(ROOT, "osu_allreduce").API_SPAN
READERS = ("api_self_us.busbw", "launch_us.busbw",
           "launch_to_device_us.busbw", "recycle_hit_share.busbw")


def synthetic(with_lib: bool = True):
    """Two calls on two chips, in ns: the first cold (resolve, then
    launch), the second hot.  Chip 1 starts each op 10 ns after chip 0,
    and the second call's op begins inside ``bench.wait`` on chip 1."""
    devices = {0: [(185, 300, "ar"), (560, 650, "ar")],
               1: [(195, 300, "ar"), (570, 650, "ar")]}
    spans = {"bench.window": [(0, 1000)],
             "bench.call": [(100, 200), (500, 560)],
             "bench.wait": [(200, 400), (560, 700)]}
    lib = {"ompi.api.allreduce": [(110, 190, {"seq": 0, "hot": 0,
                                              "recycled": 0}),
                                  (505, 555, {"seq": 1, "hot": 1,
                                              "recycled": 1})],
           "ompi.coll.resolve": [(120, 140, {"seq": 0})],
           "ompi.coll.launch": [(150, 180, {"seq": 0}),
                                (520, 550, {"seq": 1})]}
    return trace.Trace(devices, spans), (lib if with_lib else {})


def test_api_self_time_leaves_out_the_coll_children():
    tr, lib = synthetic()
    # call 0: 80 - 20 - 30; call 1: 50 - 30; median of 30 and 20 ns
    assert libspans.api_self_us(tr, lib, API) == pytest.approx(0.025)


def test_hot_share_and_launch_time():
    tr, lib = synthetic()
    assert libspans.api_hot_share(tr, lib, API) == 50.0
    assert libspans.launch_us(tr, lib) == pytest.approx(0.030)
    assert libspans.recycle_hit_share(tr, lib, API) == 50.0


def test_launch_to_device_averages_the_chips_then_takes_the_median():
    tr, lib = synthetic()
    # call 0: (35 + 45) / 2; call 1: (40 + 50) / 2; median of 40 and 45
    assert libspans.launch_to_device_us(tr, lib) == pytest.approx(0.0425)


def test_no_library_spans_no_numbers():
    tr, lib = synthetic(with_lib=False)
    got = libspans.numbers(tr, lib, API)
    assert got.pop("clock_offset_us") is not None
    assert got == {"api_self_us": None, "api_hot_share": None,
                   "recycle_hit_share": None, "launch_us": None,
                   "launch_to_device_us": None}


@pytest.mark.parametrize("with_lib, hi", [(True, 0.035), (False, 0.060)])
def test_causality_bounds_the_clock_offset(with_lib, hi):
    """No op starts before its launch (or, without library spans, its
    ``bench.call``) and none ends after its ``bench.wait``: chip 0's
    second op ends 50 ns before the wait does, chip 0's first starts
    35 ns after its launch and 60 ns after the second call began."""
    tr, lib = synthetic(with_lib)
    assert libspans.clock_offset_us(tr, lib) == pytest.approx([-0.050, hi])


def test_idle_goes_to_the_innermost_span():
    tr, lib = synthetic()
    got = {k: v * 1e9 for k, v in libspans.idle_by_span(tr, lib)}
    assert got == pytest.approx({
        "between_calls": 500, "bench.call": 22.5, "ompi.api.allreduce": 47.5,
        "ompi.coll.resolve": 20, "ompi.coll.launch": 60,
        "bench.wait.after_op": 150, "bench.wait.before_op": 5})
    assert sum(got.values()) == pytest.approx(
        sum(v for _, v in tr.breakdown()["idle_gaps"]) * 1e9)


def _same_as_breakdown(tr, by_span):
    """``idle_by_span`` without library spans gives ``breakdown``'s
    labels and seconds, with ``bench.wait`` split in two."""
    old = dict(tr.breakdown()["idle_gaps"])
    new = dict(by_span)
    wait = new.pop("bench.wait.before_op", 0.0) + new.pop(
        "bench.wait.after_op", 0.0) + new.pop("bench.wait", 0.0)
    assert wait == pytest.approx(old.pop("bench.wait", 0.0), rel=1e-12)
    assert new.keys() == old.keys()
    for k in old:
        assert new[k] == pytest.approx(old[k], rel=1e-12)


def test_without_library_spans_the_split_is_breakdowns():
    tr, _ = synthetic(with_lib=False)
    _same_as_breakdown(tr, libspans.idle_by_span(tr))


def load(name: str):
    import jax

    pd = jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress((FIXTURES / name).read_bytes()))
    return trace.from_profile(pd), libspans.from_profile(pd)


@pytest.mark.parametrize("name", ["device_large_v5e_2x2.xplane.pb.gz",
                                  "host_small_v5e_1chip.xplane.pb.gz"])
def test_chip_fixtures_without_library_spans(name):
    tr, lib = load(name)
    assert lib == {}
    got = libspans.numbers(tr, lib, API)
    offset = got.pop("clock_offset_us")
    assert all(v is None for v in got.values())
    by_span = libspans.idle_by_span(tr, lib)
    _same_as_breakdown(tr, by_span)
    if name.startswith("device_large"):
        labels = {k for k, _ in by_span}
        assert {"bench.wait.before_op", "bench.wait.after_op"} & labels
        # every op starts at least 0.86 ms before its call began: this
        # session put the chips' clock at least 0.86 ms behind the host's
        assert offset == pytest.approx([-1614.558, -855.684])
    else:
        assert offset is None  # the copies are not ops: none per call


@pytest.fixture(scope="module")
def device_large_lib():
    """One cycle (40 calls, 8 of each of 1-256 MiB per rank) of
    device_large on a v5e 2x2, library tracing on (seed 1618033988)."""
    return load("device_large_lib_v5e_2x2.xplane.pb.gz")


def test_four_numbers_on_the_chip_fixture(device_large_lib):
    tr, lib = device_large_lib
    assert len(lib["ompi.api.allreduce"]) == len(tr.spans["bench.call"]) == 40
    assert len(lib["ompi.coll.launch"]) == 40 and "ompi.coll.resolve" not in lib
    for s, e, st in lib["ompi.api.allreduce"]:
        assert st["comm"] == "MPI_COMM_WORLD" and st["hot"] in (0, 1)
    got = libspans.numbers(tr, lib, API)
    # recorded before the library marked its recycled calls
    assert got.pop("recycle_hit_share") is None
    assert got == pytest.approx({
        "api_self_us": 36.065, "api_hot_share": 2.5, "launch_us": 427.87,
        "launch_to_device_us": 353.522375,
        "clock_offset_us": [-452.01, 291.014]})


def test_chip_fixture_idle_by_innermost_span(device_large_lib):
    tr, lib = device_large_lib
    by_span = dict(libspans.idle_by_span(tr, lib))
    assert {"ompi.coll.launch", "ompi.api.allreduce",
            "bench.wait.after_op"} <= by_span.keys()
    gaps = sum(e - s for ops in tr.devices.values()
               for s, e in trace.gaps_ns([(s, e) for s, e, _ in ops],
                                         tr.lo, tr.hi))
    assert sum(by_span.values()) == pytest.approx(
        gaps / len(tr.devices) / 1e9, rel=1e-12)
    assert sum(by_span.values()) == pytest.approx(
        sum(v for _, v in tr.breakdown()["idle_gaps"]), rel=1e-12)


# -- the readers over the second traced window --------------------------------

def read_all(tr, lib, call=SimpleNamespace(API_SPAN=API)):
    run = SimpleNamespace(lib=(tr, lib), call=call)
    return {name: load_reader(ROOT, name)(run) for name in READERS}


def test_readers_on_the_chip_fixture(device_large_lib):
    """The values ``test_four_numbers_on_the_chip_fixture`` pins; the
    fixture's clock bounds, -452 to 291 us, are far wider than 1% of
    the 354 us from launch to the chips, and its spans carry no
    ``recycled`` arg: those two readers report nothing."""
    assert read_all(*device_large_lib) == {
        "api_self_us.busbw": pytest.approx(36.065),
        "launch_us.busbw": pytest.approx(427.87),
        "launch_to_device_us.busbw": None,
        "recycle_hit_share.busbw": None}


def test_readers_on_a_synthetic_trace():
    tr, lib = synthetic()
    assert read_all(tr, lib) == {
        "api_self_us.busbw": pytest.approx(0.025),
        "launch_us.busbw": pytest.approx(0.030),
        "launch_to_device_us.busbw": None,  # bounds -50 to 35 ns
        "recycle_hit_share.busbw": 50.0}


def tight_clock():
    """Three calls on one chip whose clock bounds hold 0 exactly: the
    first call's op starts at its launch, the third's ends as its wait
    does.  Launch to op: 0, 40 and 50 ns."""
    devices = {0: [(100, 150, "ar"), (340, 400, "ar"), (550, 600, "ar")]}
    spans = {"bench.window": [(0, 1000)],
             "bench.call": [(90, 110), (290, 310), (490, 510)],
             "bench.wait": [(110, 200), (310, 450), (510, 600)]}
    lib = {API: [(95, 105, {}), (295, 305, {}), (495, 505, {})],
           libspans.LAUNCH: [(100, 104, {}), (300, 304, {}),
                             (500, 504, {})]}
    return trace.Trace(devices, spans), lib


@pytest.mark.parametrize("lo, hi, reported", [
    (0, 0, True), (-0.0004, 0.0004, True), (-0.0005, 0, False),
    (0, 0.0005, False), (0.0001, 0.0002, False)])
def test_launch_to_device_needs_the_clocks_bounded_within_1_percent(
        monkeypatch, lo, hi, reported):
    tr, lib = tight_clock()
    assert libspans.clock_offset_us(tr, lib) == [0, 0]
    monkeypatch.setattr(libspans, "clock_offset_us", lambda *_: [lo, hi])
    got = read_all(tr, lib)["launch_to_device_us.busbw"]
    assert got == (pytest.approx(0.040) if reported else None)


@pytest.mark.parametrize("call", [SimpleNamespace(),
                                  SimpleNamespace(API_SPAN=None)],
                         ids=["no_attribute", "none"])
def test_a_call_module_with_no_api_span_gets_no_span_numbers(
        device_large_lib, call):
    assert set(read_all(*device_large_lib, call=call).values()) == {None}


def test_a_run_without_the_second_window_gets_no_span_numbers():
    run = SimpleNamespace(lib=None, call=SimpleNamespace(API_SPAN=API))
    assert {load_reader(ROOT, name)(run) for name in READERS} == {None}
