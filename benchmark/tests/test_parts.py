"""The benchmark's yardstick, part by part: the traffic generator, the
byte arithmetic and peaks, the reference, and the trace reduction."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import arith, generate, reference, trace

MiB = 1 << 20
BIG_SEED = 2**31 + 977


# -- traffic ------------------------------------------------------------------

@pytest.fixture
def large():
    return generate.load_mix(Path(__file__).resolve().parents[1]
                             / "traffic" / "large.json")


def test_cycle_is_fixed_by_the_seed(large):
    assert generate.cycle(large, BIG_SEED) == generate.cycle(large, BIG_SEED)
    assert generate.cycle(large, BIG_SEED) != generate.cycle(large, BIG_SEED + 1)


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED, 2**40 + 3])
def test_every_seed_does_the_same_work(large, seed):
    sched = generate.cycle(large, seed)
    n_sizes = len(large["sizes_bytes"])
    assert len(sched) == n_sizes * large["repeats_per_cycle"]
    for i in range(n_sizes):
        slots = [slot for s, slot in sched if s == i]
        assert len(slots) == large["repeats_per_cycle"]
        assert sorted(set(slots)) == list(range(large["inputs_per_size"]))


def test_each_round_of_a_cycle_holds_every_size_once(large):
    n_sizes = len(large["sizes_bytes"])
    sched = generate.cycle(large, BIG_SEED)
    for r in range(large["repeats_per_cycle"]):
        rnd = sched[r * n_sizes:(r + 1) * n_sizes]
        assert sorted(s for s, _ in rnd) == list(range(n_sizes))


def test_checked_positions_cover_every_size(large):
    sched = generate.cycle(large, BIG_SEED)
    picks = generate.checked_positions(large, sched, BIG_SEED)
    assert picks == generate.checked_positions(large, sched, BIG_SEED)
    assert sorted({sched[p][0] for p in picks}) == list(
        range(len(large["sizes_bytes"])))
    assert len(picks) == large["checked_per_size"] * len(large["sizes_bytes"])


def test_a_mix_that_is_not_one_closed_loop_is_refused(tmp_path, large):
    p = tmp_path / "open.json"
    p.write_text(json.dumps({**large, "loop": "open"}))
    with pytest.raises(ValueError):
        generate.load_mix(p)


@pytest.mark.parametrize("size", [6, 0, -4])
def test_an_allreduce_size_that_is_no_float32_count_is_refused(large, size):
    from benchmark.harness import ROOT, load_call

    call = load_call(ROOT, "osu_allreduce")
    call.validate(large, {})
    with pytest.raises(ValueError):
        call.validate({**large, "sizes_bytes": [4096, size]}, {})


# -- bytes and peaks ------------------------------------------------------------

def test_bus_bytes_by_hand():
    assert arith.bus_bytes(MiB, 4) == 1.5 * MiB  # 2 * 3/4
    assert arith.bus_bytes(MiB, 2) == MiB
    assert arith.bus_bytes(MiB, 1) == 0


def test_allreduce_floor_on_v5e_by_hand():
    pk = arith.peaks("TPU v5 lite", 2)
    assert pk["ici_bytes_per_s"] == 100e9  # two links of 50 GB/s
    t, bound = arith.allreduce_floor_s(256 * MiB, 4, pk)
    # 1.5 * 256 MiB / 100 GB/s beats 2 * 256 MiB / 819 GB/s
    assert bound == "ici" and t == pytest.approx(402653184 / 100e9)
    t, bound = arith.allreduce_floor_s(256 * MiB, 1,
                                       arith.peaks("TPU v5 lite", 0))
    assert bound == "hbm" and t == pytest.approx(536870912 / 819e9)


@pytest.mark.parametrize("coords, links", [
    ([(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)], 2),
    ([(0, 0, 0)], 0),
    ([(x, y, 0) for x in range(4) for y in range(2)], 2),
    ([(0, 0, 0), (1, 0, 0)], 1),
    ([(0, 0, 0), None], None),
    ([None], None)], ids=["2x2", "one_chip", "4x2", "1x2", "cpu_mixed",
                          "cpu"])
def test_ici_links_from_coords(coords, links):
    """The fewest neighbours at distance 1 that any chip has in the
    slice: a corner of a 2x2 or a 4x2 has two."""
    assert arith.ici_links(coords) == links


def test_an_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        arith.peaks("TPU v9 imaginary", 2)


# -- the reference ---------------------------------------------------------------

def test_fold_sum_by_hand():
    x = np.array([[1.0, 2.0], [3.0, -5.0], [0.5, 0.25]], np.float32)
    assert reference.fold_sum(x).tolist() == [4.5, -2.75]


def test_a_float32_sum_in_another_order_is_within_a_few_eps():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((4, 10000), dtype=np.float32)
    tree = ((x[0] + x[1]) + (x[2] + x[3]))[None].repeat(4, 0)
    assert reference.max_err_eps(x, tree) < 3


def test_a_bfloat16_sum_is_thousands_of_eps_away():
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    x = rng.standard_normal((4, 10000), dtype=np.float32)
    low = np.asarray(jnp.asarray(x, jnp.bfloat16).sum(0).astype(jnp.float32))
    assert reference.max_err_eps(x, low[None].repeat(4, 0)) > 1000


def test_one_rank_is_exact_and_any_change_is_seen():
    x = np.random.default_rng(5).standard_normal((1, 100), dtype=np.float32)
    assert reference.max_err_eps(x, x.copy()) == 0
    bad = x.copy()
    bad[0, 17] = np.nextafter(bad[0, 17], np.float32(np.inf))
    assert reference.max_err_eps(x, bad) > 0


def test_a_wrong_shape_or_nan_reads_wrong():
    x = np.ones((2, 3), np.float32)
    assert reference.max_err_eps(x, np.ones((1, 3), np.float32)) == reference.WRONG
    bad = np.full((2, 3), 2.0, np.float32)
    bad[1, 1] = np.nan
    assert reference.max_err_eps(x, bad) == reference.WRONG


# -- trace arithmetic -----------------------------------------------------------

def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 15), (20, 30), (40, 50)]
    assert trace.union_ns(iv, 0, 100) == 35
    assert trace.union_ns(iv, 8, 45) == 7 + 10 + 5
    assert trace.gaps_ns(iv, 0, 60) == [(15, 20), (30, 40), (50, 60)]


def test_busy_idle_and_breakdown_by_hand():
    t = trace.Trace(
        {0: [(10, 30, "all-reduce"), (50, 60, "copy")],
         1: [(10, 40, "all-reduce")]},
        {"bench.window": [(0, 100)], "bench.call": [(0, 10), (45, 50)],
         "bench.wait": [(10, 45), (50, 100)]})
    assert t.window_s() == 100e-9
    assert t.busy_s() == pytest.approx((30 + 30) / 2 * 1e-9)
    assert t.idle_share() == pytest.approx(0.7)
    assert t.op_seconds(lambda n: n == "all-reduce") == pytest.approx(25e-9)
    bd = t.breakdown()
    assert bd["device_ops"][0] == ["all-reduce", pytest.approx(25e-9)]
    idle = dict(bd["idle_gaps"])
    # TPU:0 idles 0-10 (call), 30-45 (wait), 45-50 (call), 60-100 (wait);
    # TPU:1 idles 0-10 (call), 40-45 (wait), 45-50 (call), 50-100 (wait)
    assert idle["bench.call"] == pytest.approx((15 + 15) / 2 * 1e-9)
    assert idle["bench.wait"] == pytest.approx((55 + 55) / 2 * 1e-9)


@pytest.mark.parametrize("op,code", [
    ("%psum_invariant.7 = f32[1,67108864]{1,0:T(1,128)} all-reduce("
     "%param.1), channel_id=1, replica_groups={{0,1,2,3}}", "all-reduce"),
    ("%copy.1 = f32[1,1024]{1,0:T(1,128)} copy(f32[1,1024]{1,0:T(1,128)} "
     "%v.1)", "copy"),
    ("%ars = (f32[8]{0}, u32[]) all-reduce-start(%p)", "all-reduce-start"),
    ("h2d", "h2d"),
])
def test_opcode_of_an_xla_ops_event(op, code):
    assert trace.opcode(op) == code
    assert trace.short(op).endswith(code)
