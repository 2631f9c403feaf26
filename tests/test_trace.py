"""Trace subsystem tests — the third observability leg (SPC counters,
monitoring matrices, and now event timelines): ring-buffer recording,
the zero-cost disabled path, Chrome export, cross-rank merge keyed by
(comm, op, seq), MPI_T trace pvars, and the trace_report CLI."""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import ompi_tpu.api as api
from ompi_tpu.op import SUM
from ompi_tpu.tool import mpit
from ompi_tpu.trace import causal, chrome, core as trace, merge

REPO = Path(__file__).resolve().parent.parent
REPORT = REPO / "tools" / "trace_report.py"
GOLDEN = REPO / "tests" / "golden" / "trace_fixture.json"

N = 8


@pytest.fixture(scope="module")
def world(devices):
    return api.init()


@pytest.fixture(autouse=True)
def clean_trace():
    trace.reset()
    trace.enable(False)
    causal.reset()
    yield
    trace.reset()
    trace.enable(False)
    causal.reset()


# -- core recording ----------------------------------------------------


def test_disabled_by_default_records_nothing(world):
    """The satellite guarantee: with trace_enable off (the default),
    every hook is a no-op — collectives, p2p, and direct record calls
    leave the buffer empty."""
    assert not trace.enabled()
    trace.instant("api", "nope")
    trace.span("api", "nope").end()
    with trace.span("api", "nope"):
        pass
    x = np.ones((N, 4), np.float32)
    world.allreduce(x, SUM)
    world.barrier()
    world.send(np.arange(3.0), source=0, dest=1, tag=9)
    world.recv(dest=1, source=0, tag=9)
    assert trace.event_count() == 0
    assert trace.dropped() == 0


def test_enabled_records_api_and_coll_spans(world):
    trace.enable(True)
    x = np.ones((N, 4), np.float32)
    world.allreduce(x, SUM)
    world.allreduce(x, SUM)
    world.barrier()
    evs = trace.events()
    spans = [(e[3], e[4], e[6]) for e in evs if e[0] == "X"]
    # api-layer allreduce spans carry incrementing seq (the merge key)
    ar = [s for s in spans if s[:2] == ("api", "allreduce")]
    assert [s[2] for s in ar] == [0, 1], spans
    assert ("api", "barrier", 0) in spans
    # coll layer present (table-path barrier names its provider)
    assert any(e[3] == "coll" for e in evs), evs
    st = trace.span_stats()
    assert st[("api", "allreduce")]["count"] == 2
    assert sum(st[("api", "allreduce")]["hist"]) == 2


def test_p2p_and_request_layers(world):
    trace.enable(True)
    world.send(np.arange(4.0), source=2, dest=3, tag=1)
    out, st = world.recv(dest=3, source=2, tag=1)
    np.testing.assert_array_equal(out, np.arange(4.0))
    layers = {e[3] for e in trace.events()}
    assert "p2p" in layers, layers
    names = [e[4] for e in trace.events() if e[3] == "p2p"]
    assert "send" in names and "irecv" in names, names


def test_ring_buffer_bounded_and_counts_drops():
    trace.enable(True, buffer_events=8)
    for i in range(20):
        trace.instant("api", f"e{i}")
    assert trace.event_count() == 8
    assert trace.dropped() == 12
    # oldest dropped: the survivors are the last 8
    assert [e[4] for e in trace.events()] == [f"e{i}" for i in range(12, 20)]
    trace.enable(True, buffer_events=65536)


def test_seq_counters_per_comm_op():
    trace.enable(True)
    assert trace.next_seq("c1", "allreduce") == 0
    assert trace.next_seq("c1", "allreduce") == 1
    assert trace.next_seq("c1", "bcast") == 0
    assert trace.next_seq("c2", "allreduce") == 0
    trace.reset()
    assert trace.next_seq("c1", "allreduce") == 0


# -- the profiler sink --------------------------------------------------


def _profile(tmp_path, fn):
    """Run ``fn`` under a jax.profiler session; the library's events on
    the host plane as (name, start_ns, end_ns, stats), in start order."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    return sorted(
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in pd.planes if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if e.name.startswith("ompi."))


def test_allreduce_spans_reach_the_profiler_cold_then_hot(world, tmp_path):
    """Tracing on, under a profiler session: a cold and a hot
    ``Comm.allreduce`` each give one ``ompi.api.allreduce`` with
    ``ompi.coll.launch`` inside it; only the cold call resolves, and it
    builds its programs, the plain one and the variant that writes into
    a dropped result (an ``ompi.coll.build`` each inside the resolve: a
    shape no earlier test used).  The list keeps the first result, so
    the hot call cannot write into it (``recycled`` 0).  The ring holds
    the same spans, in its own format, and the Chrome export keys only
    the api spans."""
    import jax

    x = jax.device_put(np.ones((N, 37), np.float32),
                       world.mesh.rank_sharding())
    trace.enable(True)
    evs = _profile(tmp_path, lambda: [jax.block_until_ready(
        world.allreduce(x, SUM)) for _ in range(2)])

    api_evs = [e for e in evs if e[0] == "ompi.api.allreduce"]
    assert [e[3]["seq"] for e in api_evs] == [0, 1]
    assert [e[3]["hot"] for e in api_evs] == [0, 1]
    assert [e[3]["recycled"] for e in api_evs] == [0, 0]
    assert {e[3]["nbytes"] for e in api_evs} == {N * 37 * 4}
    assert {e[3]["comm"] for e in api_evs} == {world.name}
    for name, s, e, st in api_evs:
        inside = [c for c in evs if c[0].startswith("ompi.coll.")
                  and c[3].get("seq") == st["seq"]]
        assert all(s <= cs and ce <= e for _, cs, ce, _ in inside), inside
        kids = [c[0] for c in inside]
        if st["hot"]:
            assert kids == ["ompi.coll.launch"]
        else:
            assert sorted(kids) == ["ompi.coll.launch", "ompi.coll.resolve"]
            (res,) = [c for c in inside if c[0] == "ompi.coll.resolve"]
            assert "comm" not in res[3]
            builds = [c for c in evs if c[0] == "ompi.coll.build"]
            assert len(builds) == 2
            for build in builds:
                assert res[1] <= build[1] and build[2] <= res[2]
                assert build[3] == {}

    ring = [(e[3], e[4], e[6], e[7]) for e in trace.events() if e[0] == "X"]
    assert sorted(f"ompi.{la}.{n}" for la, n, _, _ in ring) == sorted(
        e[0] for e in evs)
    for layer, name, seq, args in ring:
        assert seq == -1 if name == "build" else seq in (0, 1)
        if layer == "api":
            assert args["nbytes"] == N * 37 * 4 and args["hot"] in (0, 1)
    doc = chrome.to_chrome(trace.events(), trace.epoch())
    assert merge.collective_keys(doc) == [
        (world.name, "allreduce", 0), (world.name, "allreduce", 1)]
    assert trace.span_stats()[("coll", "launch")]["count"] == 2
    assert trace.span_stats()[("coll", "build")]["count"] == 2


def test_tracing_off_puts_nothing_on_the_profiler(world, tmp_path):
    import jax

    x = jax.device_put(np.ones((N, 5), np.float32),
                       world.mesh.rank_sharding())
    evs = _profile(tmp_path, lambda: [jax.block_until_ready(
        world.allreduce(x, SUM)) for _ in range(2)])
    assert evs == []
    assert trace.event_count() == 0


def test_span_api_without_a_profiler_session():
    """The ring alone when no profiler records: one record per span,
    args given at open, while open and at close; a child shares the
    parent's seq and takes no comm; a span whose body raised is still
    closed and recorded; one that outlives tracing records nothing."""
    trace.enable(True)
    with trace.span("api", "bcast", comm="c", seq=4, nbytes=8) as sp:
        with sp.child("coll", "resolve", cache="hit"):
            pass
        sp.args["hot"] = 0
    sp2 = trace.span("p2p", "send", dst=1)
    sp2.args["tag"] = 3
    sp2.end(matched=False)
    with pytest.raises(RuntimeError):
        with trace.span("request", "wait"):
            raise RuntimeError("peer failed")
    late = trace.span("dcn", "send")
    trace.enable(False)
    late.end()
    evs = trace.events()
    assert [(e[3], e[4], e[5], e[6], e[7]) for e in evs] == [
        ("coll", "resolve", "", 4, {"cache": "hit"}),
        ("api", "bcast", "c", 4, {"nbytes": 8, "hot": 0}),
        ("p2p", "send", "", -1, {"dst": 1, "tag": 3, "matched": False}),
        ("request", "wait", "", -1, None),
    ]
    assert evs[0][1] >= evs[1][1] and evs[0][2] <= evs[1][2]
    assert set(trace.span_stats()) == {("coll", "resolve"), ("api", "bcast"),
                                       ("p2p", "send"), ("request", "wait")}


# -- chrome export + merge ---------------------------------------------


def _record_rank(ops=3):
    for _ in range(ops):
        with trace.span("api", "allreduce", comm="MPI_COMM_WORLD",
                        seq=trace.next_seq("MPI_COMM_WORLD", "allreduce"),
                        nbytes=64):
            with trace.span("coll", "allreduce", provider="han"):
                pass
            with trace.span("dcn", "send", nbytes=64, peer="x",
                            proto="eager"):
                pass


def test_chrome_export_valid(tmp_path):
    trace.enable(True)
    _record_rank()
    p = tmp_path / "t.json"
    chrome.dump(str(p), pid=0)
    doc = json.load(open(p))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 9
    for e in xs:
        assert {"name", "cat", "pid", "tid", "ts", "dur"} <= set(e)
    # thread metadata names the layers
    lanes = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"api", "coll", "dcn"} <= lanes
    assert doc["otherData"]["dropped_events"] == 0


def test_merge_aligns_ranks(tmp_path):
    paths = []
    for rank in range(2):
        trace.reset()
        trace.enable(True)
        _record_rank()
        p = tmp_path / f"trace.{rank}.json"
        chrome.dump(str(p), pid=rank)
        paths.append(str(p))
    merged = merge.merge_files(paths)
    assert merged["otherData"]["merged_processes"] == [0, 1]
    k0 = merge.collective_keys(merged, pid=0)
    k1 = merge.collective_keys(merged, pid=1)
    assert k0 == k1 == [("MPI_COMM_WORLD", "allreduce", i) for i in range(3)]
    # keyed spans carry the cross-rank selection key
    keyed = [e for e in merged["traceEvents"]
             if (e.get("args") or {}).get("key")]
    assert len(keyed) == 6  # 3 collectives × 2 ranks
    # timestamps sorted in the merged timeline
    ts = [e["ts"] for e in merged["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)


# -- MPI_T pvars -------------------------------------------------------


def test_mpit_trace_pvars(world):
    mpit.init_thread()
    try:
        trace.enable(True)
        x = np.ones((N, 2), np.float32)
        world.allreduce(x, SUM)
        assert mpit.pvar_read(mpit.pvar_index("trace_events")) >= 1
        assert mpit.pvar_read(mpit.pvar_index("trace_dropped")) == 0
        # pvars key on (layer, op): p2p 'send' and dcn 'send' never merge
        i = mpit.pvar_index("trace_span_api_allreduce_count")
        assert mpit.pvar_read(i) == 1
        h = mpit.pvar_index("trace_span_api_allreduce_hist")
        buckets = mpit.pvar_read(h)
        assert isinstance(buckets, list) and sum(buckets) == 1
        assert mpit.pvar_get_info(h).var_class == mpit.PVAR_CLASS_AGGREGATE
        # pvar_reset zeroes aggregates but PRESERVES the event ring
        # (the finalize-time timeline must not be truncated by a
        # counter reset), the seq counters, and the namespace (cached
        # indices stay valid)
        n_names = mpit.pvar_get_num()
        ring = mpit.pvar_read(mpit.pvar_index("trace_events"))
        before = trace.next_seq("MPI_COMM_WORLD", "allreduce")
        mpit.pvar_reset()
        assert mpit.pvar_read(mpit.pvar_index("trace_events")) == ring
        assert mpit.pvar_read(i) == 0  # same handle, same variable
        assert mpit.pvar_get_num() == n_names
        assert trace.next_seq("MPI_COMM_WORLD", "allreduce") == before + 1
        # single-handle reset (the C MPI_T_pvar_reset path): zeroes only
        # that aggregate; other pvars and the event ring are untouched
        world.allreduce(x, SUM)
        assert mpit.pvar_read(i) == 1
        ring_before = mpit.pvar_read(mpit.pvar_index("trace_events"))
        mpit.pvar_reset_one(i)
        assert mpit.pvar_read(i) == 0
        assert mpit.pvar_read(mpit.pvar_index("trace_events")) == ring_before
        # trace_events is a watermark: resetting it would truncate the
        # finalize-time trace file, so it refuses
        from ompi_tpu.core.errors import MPIArgError

        with pytest.raises(MPIArgError):
            mpit.pvar_reset_one(mpit.pvar_index("trace_events"))
    finally:
        mpit.finalize()


# -- trace_report CLI --------------------------------------------------


def test_trace_report_selftest():
    """CI satellite: the CLI's built-in self-check must pass."""
    res = subprocess.run([sys.executable, str(REPORT), "--selftest"],
                         capture_output=True, timeout=60)
    assert res.returncode == 0, res.stderr.decode()
    assert b"selftest OK" in res.stdout


def test_trace_report_golden_fixture(tmp_path):
    """CI satellite: report + merge over the checked-in golden trace."""
    out = tmp_path / "merged.json"
    res = subprocess.run(
        [sys.executable, str(REPORT), str(GOLDEN), "--merge-out", str(out)],
        capture_output=True, timeout=60,
    )
    assert res.returncode == 0, res.stderr.decode()
    text = res.stdout.decode()
    assert "allreduce" in text and "p99" in text and "slowest" in text
    doc = json.load(open(out))  # merged output is valid Chrome JSON
    assert doc["otherData"]["merged_processes"] == [0, 1]
    k0 = merge.collective_keys(doc, pid=0)
    k1 = merge.collective_keys(doc, pid=1)
    assert k0 == k1 != []


# -- multi-process (tpurun) end-to-end ---------------------------------


def test_tpurun_np2_trace_merge(tmp_path):
    """The acceptance run: a 2-rank multiproc job with trace_enable on
    writes per-rank Chrome traces whose merged timeline has the same
    collective (comm, op, seq) sequence on both ranks, spans from ≥3
    layers for the allreduces, monotonic per-rank timestamps, and a
    trace_report summary."""
    from tests.test_multiproc import run_tpurun

    out_base = tmp_path / "trace"
    res = run_tpurun(
        2, REPO / "tests" / "workers" / "mp_trace_worker.py", cpu_devices=1,
        mca={"trace_enable": "1", "trace_output": str(out_base),
             "btl": "tcp"},
    )
    out = res.stdout.decode()
    assert res.returncode == 0, f"tpurun failed:\n{out}\n{res.stderr.decode()}"
    for check in ("trace_allreduce", "trace_bcast_barrier", "trace_layers",
                  "finalize"):
        hits = [l for l in out.splitlines() if f"OK {check} " in l]
        assert len(hits) == 2, f"{check}: {hits}\n{out}"

    paths = [f"{out_base}.{p}.json" for p in range(2)]
    for p in paths:
        assert Path(p).exists(), f"missing per-rank trace {p}\n{out}"
        json.load(open(p))  # each rank file is valid Chrome JSON
    merged = merge.merge_files(paths)
    assert merged["otherData"]["merged_processes"] == [0, 1]

    # identical collective key sequences on both ranks, ≥3 allreduces
    k0 = merge.collective_keys(merged, pid=0)
    k1 = merge.collective_keys(merged, pid=1)
    assert k0 == k1 != [], (k0, k1)
    ar = [k for k in k0 if k[1] == "allreduce"]
    assert [s for _, _, s in ar] == list(range(len(ar))) and len(ar) >= 3, k0

    # spans from ≥3 distinct layers (api, coll, dcn/p2p)
    cats = {e["cat"] for e in merged["traceEvents"] if e.get("ph") == "X"}
    assert len(cats & {"api", "coll", "dcn", "p2p"}) >= 3, cats

    # per-rank timestamps are monotonic in issue order
    for pid in (0, 1):
        ts = [e["ts"] for e in merged["traceEvents"]
              if e.get("ph") == "X" and e["pid"] == pid
              and e.get("cat") == "api" and e["name"] == "allreduce"]
        assert ts == sorted(ts), ts

    # the report renders a per-op latency summary from the merged run
    rep = subprocess.run([sys.executable, str(REPORT)] + paths,
                         capture_output=True, timeout=60)
    assert rep.returncode == 0, rep.stderr.decode()
    assert "allreduce" in rep.stdout.decode()


def test_tpurun_np2_trace_disabled_writes_nothing(tmp_path):
    """trace_output without trace_enable: hooks stay off, no files."""
    from tests.test_multiproc import run_tpurun

    out_base = tmp_path / "trace"
    res = run_tpurun(
        2, REPO / "tests" / "workers" / "mp_worker.py", cpu_devices=1,
        mca={"trace_output": str(out_base), "btl": "tcp"},
    )
    assert res.returncode == 0, res.stdout.decode() + res.stderr.decode()
    assert not list(tmp_path.glob("trace.*.json"))


# -- causal tracing (cross-rank critical path) --------------------------

MS = 1_000_000


def _engine_pair():
    from ompi_tpu.dcn.collops import DcnCollEngine

    e0 = DcnCollEngine(0, 2)
    e1 = DcnCollEngine(1, 2)
    addrs = [e0.address, e1.address]
    e0.set_addresses(addrs)
    e1.set_addresses(addrs)
    return e0, e1


def _capture_envs(eng):
    """Wrap the transport's send to record every envelope it ships."""
    envs = []
    orig = eng.transport.send

    def spy(address, envelope, payload):
        envs.append(dict(envelope))
        return orig(address, envelope, payload)

    eng.transport.send = spy
    return envs


def test_causal_disabled_zero_wire_bytes_zero_work():
    """The acceptance's disabled half: with trace_causal off (the
    default) the coll envelope carries NO context key — frames are
    byte-identical to a build without the feature — and the causal
    counters never move."""
    assert not causal.enabled()
    e0, e1 = _engine_pair()
    envs = _capture_envs(e0)
    try:
        import threading

        from ompi_tpu.op import SUM as _SUM

        t = threading.Thread(
            target=lambda: e1.allreduce(np.ones(4), _SUM, cid=11))
        t.start()
        e0.allreduce(np.ones(4), _SUM, cid=11)
        t.join()
        assert envs, "spy saw no frames"
        for env in envs:
            assert "tc" not in env, env
            # the full envelope shape a pre-causal build ships
            assert set(env) <= {"kind", "cid", "seq", "src", "meta"}, env
        assert causal.counters_snapshot() == {
            "records": 0, "sends": 0, "recvs": 0, "dropped": 0}
        assert causal.recent() == []
    finally:
        e0.close()
        e1.close()


def test_causal_context_flows_on_python_plane():
    """Enabled: every coll frame carries the versioned context, both
    sides record edges, and the recv edges name the sender's hop."""
    causal.enable(True)
    e0, e1 = _engine_pair()
    envs = _capture_envs(e0)
    try:
        import threading

        from ompi_tpu.op import SUM as _SUM

        def run(eng):
            causal.begin_op("W", "allreduce", 0)
            eng.allreduce(np.ones(4), _SUM, cid=12)
            causal.end_op()

        t = threading.Thread(target=run, args=(e1,))
        t.start()
        run(e0)
        t.join()
        assert envs and all("tc" in env for env in envs), envs
        for env in envs:
            v, comm, op, seq, hop = env["tc"]
            assert (v, comm, op, seq) == (causal.CTX_VERSION, "W",
                                          "allreduce", 0), env["tc"]
        c = causal.counters_snapshot()
        assert c["records"] == 2 and c["sends"] >= 2 and c["recvs"] >= 2, c
        recs = causal.recent()
        assert len(recs) == 2
        for rec in recs:
            assert rec[0] == "W/allreduce/0"
            assert rec[4], "no send edges"     # sends
            assert rec[5], "no recv edges"     # recvs
            for _src, hop, _t, wait in rec[5]:
                assert hop >= 0 and wait >= 0
    finally:
        e0.close()
        e1.close()


def test_causal_native_plane_meta_ride_and_c_mirror():
    """Native plane: the context rides the frame's meta-JSON region
    end-to-end (send → C wire → recv pops it before the meta reaches
    consumers), and the C schema mirror agrees with CTX_FIELDS."""
    from tests.test_faultsim import _native

    native = _native()
    lib = native.load_library()
    assert lib.tdcn_trace_ctx_version() == causal.CTX_VERSION
    assert (lib.tdcn_trace_ctx_fields().decode()
            == ",".join(causal.CTX_FIELDS))
    causal.enable(True)
    a = native.NativeDcnEngine(0, 2)
    b = native.NativeDcnEngine(1, 2)
    addrs = [a.address, b.address]
    a.set_addresses(addrs)
    b.set_addresses(addrs)
    try:
        causal.begin_op("W", "bcast", 3)
        a._send(1, "cx", 0, np.arange(8, dtype=np.float64),
                meta={"user": 1})
        causal.end_op()
        causal.begin_op("W", "bcast", 3)
        env, payload = b._recv_full(0, "cx", 0, timeout=30)
        causal.end_op()
        assert np.allclose(payload, np.arange(8.0))
        # the user meta survives, the reserved tc key does not
        assert env.get("meta") == {"user": 1}, env
        recs = causal.recent()
        recvs = [r[5] for r in recs if r[5]]
        assert recvs and recvs[0][0][:2] == [0, 0], recs  # src 0, hop 0
    finally:
        a.close()
        b.close()


def test_causal_solver_critical_path_and_tie_preference():
    """Solver semantics the golden fixture doesn't isolate: the
    backward walk, the near-tie upstream preference, an outright
    transport dominance, and the dma-wait carve."""
    def inst(r0, r1):
        return causal.instances_from_records({0: [r0], 1: [r1]})

    k = "W/allreduce/0"
    # (a) near-tie: rank 1 shows ~30 ms transport AND 30 ms skew —
    # the upstream cause wins within TIE_FACTOR
    r0 = [k, 0, 31 * MS, "x", [[0, 31 * MS, 1]],
          [[1, 0, 30 * MS, 30 * MS]], {}]
    r1 = [k, 30 * MS, 61 * MS, "x", [[0, 30 * MS, 0]],
          [[0, 0, 61 * MS, 31 * MS]], {}]
    cp = causal.critical_path(inst(r0, r1)[k])
    assert cp["dominant"] == {"rank": 1, "cause": "arrival-skew",
                              "ns": 30 * MS}, cp["dominant"]
    assert cp["makespan_ns"] == 61 * MS
    # (b) outright transport dominance (no skew): a 40 ms delivery
    # stall with on-time arrivals blames the wire, not the rank entry
    r0 = [k, 0, 41 * MS, "x", [[0, 1 * MS, 1]], [], {}]
    r1 = [k, 0, 41 * MS, "x", [],
          [[0, 0, 41 * MS, 40 * MS]], {}]
    cp = causal.critical_path(inst(r0, r1)[k])
    assert cp["dominant"]["cause"] == "transport", cp
    assert cp["dominant"]["rank"] == 1
    # (c) dma carve: the same wire wait with a measured 35 ms DMA wait
    # reclassifies into dma-wait
    r1c = [k, 0, 41 * MS, "x", [],
           [[0, 0, 41 * MS, 40 * MS]], {"dma": 35 * MS}]
    cp = causal.critical_path(inst(r0, r1c)[k])
    assert cp["per_rank"][1]["dma-wait"] == 35 * MS, cp["per_rank"]
    assert cp["dominant"]["cause"] == "dma-wait", cp["dominant"]
    # (d) ring/cts carve comes out of the sending rank's local
    # compute once the walk jumps to it (the recv waited for a send
    # issued after the receiver was ready)
    r0d = [k, 0, 50 * MS, "x", [[0, 49 * MS, 1]], [],
           {"ring": 20 * MS, "cts": 5 * MS}]
    r1d = [k, 0, 50 * MS, "x", [],
           [[0, 0, 50 * MS, 5 * MS]], {}]
    cp = causal.critical_path(inst(r0d, r1d)[k])
    pr = cp["per_rank"]
    assert pr[0].get("ring-backpressure") == 20 * MS, pr
    assert pr[0].get("cts-wait") == 5 * MS, pr
    # incomplete instances are skipped by solve() under nprocs
    out = causal.solve(inst(r0, r1), nprocs=3)
    assert out["instances"] == 0


def test_tpurun_np2_causal_critical_path_tri_surface(tmp_path):
    """THE acceptance run: trace_causal + telemetry + metrics on, a
    faultsim ``delay:ms=30;site=recv;proc=1`` plan making rank 1 the
    straggler.  The critical path's dominant segment must name
    (rank 1, arrival-skew) IDENTICALLY on all three surfaces: the
    live /critical scrape mid-job, the offline
    ``trace_report.py --critical-path`` over the finalize trace
    files, and the finalize metrics JSONL's causal export joined
    through ``causal.profile_from_records``."""
    import os
    import threading
    import time
    import urllib.request

    out_trace = tmp_path / "trace"
    out_metrics = tmp_path / "m"
    cmd = [sys.executable, "-m", "ompi_tpu", "run", "-np", "2",
           "--cpu-devices", "1",
           "--mca", "trace_causal", "1",
           "--mca", "trace_output", str(out_trace),
           "--mca", "metrics_enable", "1",
           "--mca", "metrics_output", str(out_metrics),
           "--mca", "telemetry_enable", "1",
           "--mca", "telemetry_interval_ms", "150",
           "--mca", "btl", "tcp",
           "--mca", "faultsim_enable", "1",
           "--mca", "faultsim_seed", "3",
           "--mca", "faultsim_plan", "delay:ms=30;site=recv;proc=1",
           str(REPO / "tests" / "workers" / "mp_causal_worker.py")]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + ":" + env.get("PYTHONPATH", "")
    env["CAUSAL_RUN_SECS"] = "6"
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, env=env,
                            cwd=str(REPO))
    lines: list[str] = []

    def _reader():
        for raw in iter(proc.stdout.readline, b""):
            lines.append(raw.decode(errors="replace"))

    t = threading.Thread(target=_reader, daemon=True)
    t.start()
    live_state = None
    try:
        url = None
        deadline = time.monotonic() + 60
        while url is None and time.monotonic() < deadline:
            for l in list(lines):
                if "[tpurun] telemetry: " in l:
                    url = (l.split("[tpurun] telemetry: ", 1)[1]
                           .split("/metrics", 1)[0])
                    break
            time.sleep(0.05)
        assert url, "tpurun never printed the telemetry endpoint:\n" \
            + "".join(lines)

        # surface 1 — LIVE: scrape /critical mid-job until enough
        # instances joined for a stable aggregate (the first few
        # instances are warmup: skew hasn't built yet, so their
        # paths are transport-only — 24 joins ≈ 1 s into the run)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and proc.poll() is None:
            try:
                with urllib.request.urlopen(url + "/critical",
                                            timeout=3) as r:
                    state = json.loads(r.read().decode())
            except OSError:
                time.sleep(0.2)
                continue
            if state.get("instances", 0) >= 24:
                live_state = state
                break
            time.sleep(0.2)
        assert live_state is not None and proc.poll() is None, (
            "no mid-job /critical scrape with joined instances:\n"
            + "".join(lines))
        assert live_state["dominant"]["rank"] == 1, live_state["dominant"]
        assert live_state["dominant"]["cause"] == "arrival-skew", (
            live_state["dominant"], live_state["per_rank"])
        # rank 1's on-path time dominates rank 0's
        pr = live_state["per_rank"]
        assert (sum(pr["1"].values())
                > 3 * sum(pr.get("0", {}).values())), pr
        # the /json brief agrees (the top.py blame column feed)
        with urllib.request.urlopen(url + "/json", timeout=3) as r:
            jstate = json.loads(r.read().decode())
        crit = jstate["critical"]["per_rank"]
        assert crit["1"]["cause"] == "arrival-skew", crit
        assert proc.wait(timeout=180) == 0, "".join(lines)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)
        t.join(timeout=10)
    out = "".join(lines)
    assert len([l for l in out.splitlines()
                if "OK causal proc=" in l]) == 2, out
    assert len([l for l in out.splitlines() if "OK finalize" in l]) == 2

    # surface 2 — OFFLINE: trace_report --critical-path over the
    # finalize trace files names the same dominant segment
    paths = [f"{out_trace}.{p}.json" for p in range(2)]
    for p in paths:
        assert Path(p).exists(), out
    rep = subprocess.run(
        [sys.executable, str(REPORT)] + paths + ["--critical-path"],
        capture_output=True, timeout=120)
    assert rep.returncode == 0, rep.stderr.decode()
    rtext = rep.stdout.decode()
    assert "causal critical path:" in rtext, rtext
    assert "dominant: rank 1 cause=arrival-skew" in rtext, rtext

    # surface 3 — FINALIZE EXPORT: join the per-rank causal sections
    # from the metrics JSONL exports through the same solver
    records_by_proc = {}
    counters_by_proc = {}
    for p in range(2):
        rows = [json.loads(l) for l in
                open(f"{out_metrics}.{p}.jsonl") if l.strip()]
        snap = rows[-1]
        assert snap.get("reason") == "finalize", snap.get("reason")
        records_by_proc[p] = snap.get("causal") or []
        counters_by_proc[p] = snap.get("causal_counters") or {}
        assert records_by_proc[p], f"rank {p}: empty causal export"
        assert counters_by_proc[p].get("records", 0) > 0
        # the .prom twin renders the trace_causal_* family
        prom = open(f"{out_metrics}.{p}.prom").read()
        assert "ompi_tpu_trace_causal_records" in prom
    offline = causal.profile_from_records(records_by_proc)
    assert offline["instances"] >= 8, offline["instances"]
    assert offline["dominant"]["rank"] == 1, offline["dominant"]
    assert offline["dominant"]["cause"] == "arrival-skew", (
        offline["dominant"], offline["per_rank"])


def test_causal_pvars_and_reset(world):
    """trace_causal_* pvars: fixed segment, readable, reset in place
    (session-wide and per-handle)."""
    mpit.init_thread()
    try:
        names = [mpit.pvar_get_info(i).name
                 for i in range(mpit.pvar_get_num())]
        for k in causal.PVARS:
            assert f"trace_causal_{k}" in names, k
        causal.enable(True)
        causal.begin_op("W", "allreduce", 0)
        causal.note_send(1)
        causal.end_op()
        idx = mpit.pvar_index("trace_causal_sends")
        assert mpit.pvar_read(idx) == 1
        mpit.pvar_reset_one(idx)
        assert mpit.pvar_read(idx) == 0
        assert mpit.pvar_read(
            mpit.pvar_index("trace_causal_records")) == 1
        mpit.pvar_reset()
        assert mpit.pvar_read(
            mpit.pvar_index("trace_causal_records")) == 0
    finally:
        mpit.finalize()


def test_device_window_reclaim_on_peer_failure(tmp_path):
    """Satellite: a receiver dying between RTS and consume no longer
    leaks its window — note_proc_failed reclaims exactly the dead
    peer's staged windows, counts dcn_device_window_reclaimed, and
    flight-records each one (naming the staging op when causal
    tracing captured it)."""
    from multiprocessing import shared_memory

    from ompi_tpu.dcn import device
    from ompi_tpu.metrics import core as mcore, flight

    mcore.enable(True)
    causal.enable(True)
    dp = device.DevicePlane(0, min_size=1)
    try:
        causal.begin_op("W", "bcast", 7)
        d_dead = dp.stage(np.arange(32, dtype=np.float64), dst_proc=1)
        d_live = dp.stage(np.arange(16, dtype=np.float64), dst_proc=2)
        causal.end_op()
        assert d_dead and d_live and dp.pending_windows() == 2
        # the engine hook: marking proc 1 failed reclaims ITS window
        from ompi_tpu.dcn.collops import DcnCollEngine

        eng = DcnCollEngine.__new__(DcnCollEngine)
        eng._failed_procs = set()
        eng._device_plane = dp
        DcnCollEngine.note_proc_failed(eng, 1)
        assert dp.pending_windows() == 1
        assert dp.stats["device_window_reclaimed"] == 1
        # the dead peer's segment is gone; the live peer's survives
        with pytest.raises(FileNotFoundError):
            shared_memory.SharedMemory(name=d_dead["w"], create=False)
        seg = shared_memory.SharedMemory(name=d_live["w"], create=False)
        seg.close()
        recs = [r for r in flight.records()
                if r.get("reason") == "device_window_reclaimed"]
        assert recs, flight.records()
        detail = recs[0].get("detail") or {}
        assert detail.get("proc") == 1, recs[0]
        assert detail.get("op") == "W/bcast/7", recs[0]
        # idempotent: a second mark finds nothing to reclaim
        DcnCollEngine.note_proc_failed(eng, 1)
        assert dp.stats["device_window_reclaimed"] == 1
        # the mark is remembered: staging toward the corpse degrades
        # to the host plane instead of opening a doomed window (closes
        # the stage-vs-mark race both ways)
        fb0 = dp.stats["device_fallbacks"]
        assert dp.stage(np.arange(8, dtype=np.float64),
                        dst_proc=1) is None
        assert dp.stats["device_fallbacks"] == fb0 + 1
        assert dp.pending_windows() == 1  # still only the live window
        # recover/heal clears the mark: windows flow again
        DcnCollEngine.note_proc_healed(eng, 1)
        d_back = dp.stage(np.arange(8, dtype=np.float64), dst_proc=1)
        assert d_back is not None and dp.pending_windows() == 2
    finally:
        dp.close()
        mcore.enable(False)
        flight.reset()
