"""Cross-language flight recorder: native trace rings drained into the
Python tracer, clock alignment across the language boundary and per-era
phase attribution.

The determinism tests pin the ISSUE-6 contract: two identical seeded
runs must produce identical native event SEQUENCES (kinds/lanes/args —
timestamps excluded, they are wall-clock), because the rings sit on the
same deterministic engine the bit-identity tests already pin.
"""
import json
import random

import pytest

from lachain_tpu.utils import metrics, tracing

pytestmark = pytest.mark.observability


@pytest.fixture(autouse=True)
def _clean():
    tracing.reset_for_tests()
    metrics.reset_all_for_tests()
    yield
    tracing.reset_for_tests()
    metrics.reset_all_for_tests()


class _Rng:
    def __init__(self, seed=1):
        self._r = random.Random(seed)

    def randbelow(self, n):
        return self._r.randrange(n)


def _run_native_hb(era_span: bool = True):
    """One seeded HoneyBadger era on the native engine; returns the
    drained native events (the network is closed before return)."""
    from lachain_tpu.consensus import messages as M
    from lachain_tpu.consensus.keys import trusted_key_gen
    from lachain_tpu.consensus.native_rt import NativeSimulatedNetwork

    n, f = 4, 1
    pub, privs = trusted_key_gen(n, f, rng=_Rng(7))
    net = NativeSimulatedNetwork(pub, privs, era=0, seed=11)
    pid = M.HoneyBadgerId(era=0)

    def drive():
        for i in range(n):
            net.post_request(i, pid, b"payload|%d|" % i + bytes(16))
        assert net.run(
            lambda: all(r.result_of(pid) is not None for r in net.routers)
        )

    if era_span:
        with tracing.span("era", era=0):
            drive()
    else:
        drive()
    evs = tracing.native_snapshot()
    net.close()
    return evs


def _signature(evs):
    """Determinism signature: everything except wall-clock values (the
    ring's records carry none in their args)."""
    return [
        (e["name"], e["cat"], e["tid"], tuple(sorted(e["args"].items())))
        for e in evs
    ]


def test_native_drain_deterministic_across_identical_runs():
    first = _signature(_run_native_hb())
    tracing.reset_for_tests()
    second = _signature(_run_native_hb())
    assert first, "native ring produced no events"
    assert first == second


def test_native_events_inside_enclosing_era_span():
    """Clock alignment: after the offset handshake, no native event may
    land outside the Python era span that encloses the whole run."""
    evs = _run_native_hb(era_span=True)
    era = next(
        s for s in tracing.snapshot() if s["name"] == "era"
    )
    assert not era["open"]
    eps = 5e-3  # ring flush happens inside the span; 5 ms covers jitter
    consensus = [e for e in evs if e["pid"] == 2]
    assert consensus
    for e in consensus:
        assert e["start"] >= era["start"] - eps, e
        assert e["end"] <= era["end"] + eps, e
        assert e["end"] >= e["start"]


def test_merged_chrome_trace_has_named_native_threads():
    """Acceptance shape: native engine events render under their own pid
    with labeled thread rows next to the Python host lanes."""
    _run_native_hb()
    out = tracing.to_chrome_trace()
    x = [e for e in out["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in out["traceEvents"] if e["ph"] == "M"]
    native = [e for e in x if e["pid"] == 2]
    assert native, "no native events in the merged export"
    assert any(e["pid"] == 1 for e in x), "python host lanes missing"
    procs = {
        m["pid"]: m["args"]["name"]
        for m in meta
        if m["name"] == "process_name"
    }
    assert procs.get(2) == "native-consensus"
    threads = {
        (m["pid"], m["tid"]): m["args"]["name"]
        for m in meta
        if m["name"] == "thread_name"
    }
    for e in native:
        assert (e["pid"], e["tid"]) in threads
    json.loads(json.dumps(out))


def test_era_report_phases_sum_to_wall_time():
    """Attribution invariant: phases + idle ≈ era wall time (<=10% off,
    the acceptance tolerance) and the known-busy phases are non-zero on
    a native run."""
    _run_native_hb(era_span=True)
    report = tracing.era_report()
    assert [e["era"] for e in report["eras"]] == [0]
    ent = report["eras"][0]
    assert ent["wall_s"] > 0
    total = sum(ent["phases_s"].values()) + ent["idle_s"]
    assert abs(total - ent["wall_s"]) <= 0.10 * ent["wall_s"]
    # TPKE share verification crosses into Python on every native run
    assert ent["phases_s"]["tpke_verify"] > 0
    # and the engine's dispatch seconds give the rbc/ba split
    assert ent["phases_s"]["rbc"] > 0


def test_era_report_table_renders():
    _run_native_hb(era_span=True)
    table = tracing.era_report_table()
    lines = table.splitlines()
    assert len(lines) >= 3  # header, rule, one era row
    for col in ("era", "wall_s", "rbc", "tpke_verify", "idle_s"):
        assert col in lines[0]


def test_idle_decomposition_sums_to_old_idle():
    """ISSUE-16 invariant: the idle column decomposes into named wait
    buckets + idle_unattributed, buckets + remainder == the old idle
    value, phases + buckets + remainder == era wall (within the 10%
    attribution tolerance), and the recorder explains most of the idle
    (unattributed <= 20% of it)."""
    _run_native_hb(era_span=True)
    ent = tracing.era_report()["eras"][0]
    assert set(ent["waits_s"]) == set(tracing.WAIT_RESOURCES)
    wsum = sum(ent["waits_s"].values())
    # exact decomposition (modulo per-field rounding at 6 decimals)
    assert abs(wsum + ent["idle_unattributed_s"] - ent["idle_s"]) < 1e-4
    total = sum(ent["phases_s"].values()) + wsum + ent["idle_unattributed_s"]
    assert abs(total - ent["wall_s"]) <= 0.10 * ent["wall_s"]
    # the whole point: idle is explained, not reported
    assert ent["idle_unattributed_s"] <= 0.20 * max(ent["idle_s"], 1e-9)
    assert ent["waits_s"]["crypto_flush"] > 0  # the N=4 era's real wait
    assert 0.0 <= ent["idle_unattributed_fraction"] <= 0.20


def _quiesce_net():
    """Seeded native net driven to quiescence: every further run() call
    re-enters the starved dispatch loop and emits one sched wait record."""
    from lachain_tpu.consensus import messages as M
    from lachain_tpu.consensus.keys import trusted_key_gen
    from lachain_tpu.consensus.native_rt import NativeSimulatedNetwork

    pub, privs = trusted_key_gen(4, 1, rng=_Rng(7))
    net = NativeSimulatedNetwork(pub, privs, era=0, seed=11)
    pid = M.HoneyBadgerId(era=0)
    for i in range(4):
        net.post_request(i, pid, b"payload|%d|" % i + bytes(16))
    net.run(lambda: False)  # drain until the queue is empty
    return net


def test_wait_record_drain_determinism():
    """Starving the dispatch loop emits wait:sched records whose SEQUENCE
    (kind/resource/era — durations are wall-clock) is identical across
    identically-seeded runs."""

    def one_run():
        net = _quiesce_net()
        for _ in range(3):
            net.run(lambda: False)  # each starved pump emits one record
        evs = tracing.native_snapshot()
        net.close()
        waits = [e for e in evs if e["cat"] == "native.wait"]
        assert len(waits) >= 3
        for e in waits:
            assert e["name"] == "wait:sched"
            assert e["args"]["resource"] == "sched"
            assert e["tname"] == "dispatch"
        return _signature(waits)

    first = one_run()
    tracing.reset_for_tests()
    second = one_run()
    assert first == second


def test_wait_records_covered_by_drop_counter():
    """The new record kind rides the same bounded ring: overflowing it
    with wait records grows the native drop counter, never blocks."""
    net = _quiesce_net()
    net.trace_configure(2)  # tiny ring: wait records must overwrite
    for _ in range(10):
        net.run(lambda: False)
    tracing.drain_native()
    assert net.trace_dropped() > 0
    assert (
        metrics.counter_value(
            "trace_events_dropped_total", labels={"source": "consensus"}
        )
        > 0
    )
    # the survivors in the tiny ring are the newest wait records
    evs = tracing.native_snapshot()
    assert any(e["name"] == "wait:sched" for e in evs)
    net.close()


def _syn_span(name, start, end, cat="era", **args):
    return {
        "id": 0,
        "name": name,
        "cat": cat,
        "start": float(start),
        "end": float(end),
        "open": False,
        "args": args,
    }


def test_critical_path_on_synthetic_known_chain():
    """Synthetic trace with a known longest chain: era [0,10] = rbc [0,4]
    -> crypto_flush wait [4,7] -> device wait [6.5,9] -> 1s gap. The walk
    must recover exactly that chain, tile the window (total == wall), and
    the decomposition must split the waits at the device-priority overlap."""
    spans = [
        _syn_span("era", 0.0, 10.0, era=0),
        _syn_span("ReliableBroadcast", 0.0, 4.0, cat="protocol", era=0),
        _syn_span("wait.crypto_flush", 4.0, 7.0, cat="wait",
                  resource="crypto_flush"),
        _syn_span("wait.device", 6.5, 9.0, cat="wait", resource="device"),
    ]
    ent = tracing.era_report(spans=spans, native=[])["eras"][0]
    assert ent["wall_s"] == pytest.approx(10.0)
    assert ent["phases_s"]["rbc"] == pytest.approx(4.0)
    assert ent["idle_s"] == pytest.approx(6.0)
    # device outranks crypto_flush on the [6.5, 7] overlap
    assert ent["waits_s"]["crypto_flush"] == pytest.approx(2.5)
    assert ent["waits_s"]["device"] == pytest.approx(2.5)
    assert ent["idle_unattributed_s"] == pytest.approx(1.0)
    assert ent["idle_unattributed_fraction"] == pytest.approx(1 / 6, abs=1e-3)
    cp = ent["critical_path"]
    assert cp["total_s"] == pytest.approx(ent["wall_s"])
    chain = [(s["kind"], s["name"]) for s in cp["segments"]]
    assert chain == [
        ("phase", "rbc"),
        ("wait", "crypto_flush"),
        ("wait", "device"),
        ("gap", "unattributed"),
    ]
    durs = [s["dur_s"] for s in cp["segments"]]
    assert durs == pytest.approx([4.0, 2.5, 2.5, 1.0])
    # renderer consumes the same block
    table = tracing.critical_path_table(
        {"eras": [ent], "phases": list(tracing.PHASES)}
    )
    assert "wait:crypto_flush" in table and "critical path 10.000s" in table


def test_trace_ring_drop_counter_python_source():
    tracing.set_capacity(8)
    try:
        for i in range(40):
            tracing.instant("tick", i=i)
    finally:
        tracing.set_capacity(tracing.DEFAULT_CAPACITY)
    assert (
        metrics.counter_value(
            "trace_events_dropped_total", labels={"source": "python"}
        )
        == 32
    )
    assert tracing.dropped_total() == 32


def test_lsm_flight_recorder_events_and_histograms(tmp_path):
    """The v2 engine numbers that were never published: WAL group-commit
    batch size + fsync latency histograms, the compaction-backlog gauge,
    and engine thread events in the merged trace."""
    from lachain_tpu.storage.lsm import LsmKV

    kv = LsmKV(str(tmp_path / "db"))
    try:
        for i in range(50):
            kv.write_batch([(b"k%04d" % i, b"v" * 64)])
        kv.flush()
        stats = kv.stats()
        assert "compact_backlog" in stats and "trace_dropped" in stats
        evs = tracing.native_snapshot()
        names = {e["name"] for e in evs}
        assert {"wal_encode", "wal_fsync", "memtable_seal"} <= names
        fsync = next(e for e in evs if e["name"] == "wal_fsync")
        assert fsync["tname"] == "wal-writer"
        assert fsync["pid"] >= 3  # own process lane, not python/consensus
        assert metrics.histogram_snapshot("lsm_wal_fsync_seconds")["count"] > 0
        gc = metrics.histogram_snapshot("lsm_wal_group_commit_records")
        assert gc["count"] > 0 and gc["sum"] >= gc["count"]
        assert metrics.gauge_value("lsm_compaction_backlog") is not None
    finally:
        kv.close()
    # the close() unregistered the source: snapshots stay quiet afterwards
    assert all(
        not s.startswith("lsm-") for s in tracing._native_sources
    )


def test_native_ring_capacity_and_drop_counter():
    """A tiny native ring overflows, the drop counter grows, and the
    drained metric reports the native source."""
    from lachain_tpu.consensus import messages as M
    from lachain_tpu.consensus.keys import trusted_key_gen
    from lachain_tpu.consensus.native_rt import NativeSimulatedNetwork

    pub, privs = trusted_key_gen(4, 1, rng=_Rng(7))
    net = NativeSimulatedNetwork(pub, privs, era=0, seed=11)
    net.trace_configure(4)  # tiny ring: events must be dropped
    pid = M.HoneyBadgerId(era=0)
    for i in range(4):
        net.post_request(i, pid, b"payload|%d|" % i + bytes(16))
    assert net.run(
        lambda: all(r.result_of(pid) is not None for r in net.routers)
    )
    tracing.drain_native()
    assert net.trace_dropped() > 0
    assert (
        metrics.counter_value(
            "trace_events_dropped_total", labels={"source": "consensus"}
        )
        > 0
    )
    net.close()


def test_rpc_and_cli_era_report_surface():
    """la_getEraReport returns the merged report shape, and the trace CLI
    accepts --era-report (the devnet runbook path)."""
    from lachain_tpu.rpc.service import RpcService

    _run_native_hb(era_span=True)
    report = RpcService.la_getEraReport(object())
    assert report["phases"] == list(tracing.PHASES)
    assert report["eras"] and report["eras"][0]["era"] == 0
    # idle decomposition + critical path ride the same RPC payload
    ent = report["eras"][0]
    assert set(ent["waits_s"]) == set(tracing.WAIT_RESOURCES)
    assert ent["critical_path"]["segments"]
    # the table renderers consume the RPC JSON round trip unchanged
    round_trip = json.loads(json.dumps(report))
    table = tracing.era_report_table(round_trip)
    assert "tpke_verify" in table.splitlines()[0]
    assert "w:crypto_flush" in table.splitlines()[0]
    cp_table = tracing.critical_path_table(round_trip)
    assert "critical path" in cp_table


# -- one timing of each thing: callbacks from cross.<op>, dispatch from the
# -- counter's own differences -------------------------------------------------


def _phase_ops(phase):
    return sorted(op for op, p in tracing._CROSS_PHASE.items() if p == phase)


@pytest.mark.parametrize("phase", sorted(set(tracing._CROSS_PHASE.values())))
def test_era_report_reads_cross_spans(phase):
    """A callback's seconds in the report are its cross.<op> span's: each
    op of the phase one second apart in a synthetic era, with no native
    event at all."""
    ops = _phase_ops(phase)
    spans = [_syn_span("era", 0.0, 100.0, era=3)] + [
        _syn_span(f"cross.{op}", 2.0 * i, 2.0 * i + 1.0, cat="engine",
                  era=3, vid=i % 4)
        for i, op in enumerate(ops)
    ]
    # other eras' callbacks and ops with no phase stay out
    spans.append(_syn_span(f"cross.{ops[0]}", 50.0, 60.0, cat="engine", era=4, vid=0))
    spans.append(_syn_span("cross.opaque_message", 70.0, 80.0, cat="engine", era=3, vid=0))
    ent = tracing.era_report(spans=spans, native=[])["eras"][0]
    assert ent["era"] == 3
    want = {p: 0.0 for p in tracing.PHASES}
    want[phase] = float(len(ops))
    assert ent["phases_s"] == pytest.approx(want)
    assert ent["idle_s"] == pytest.approx(100.0 - len(ops))
    assert [s["name"] for s in ent["critical_path"]["segments"]
            if s["kind"] == "phase"] == [phase] * len(ops)


@pytest.fixture(scope="module")
def two_native_eras():
    """Spans and the dispatch counter of a two-era N=4 native devnet."""
    from lachain_tpu.consensus.native_rt import DISPATCH_METRIC
    from lachain_tpu.core.devnet import Devnet

    tracing.reset_for_tests()
    metrics.reset_all_for_tests()
    net = Devnet(4, 1, seed=9, txs_per_block=20, engine="native", rbc_batch=True)
    try:
        net.run_eras(1, 2)
    finally:
        net.close()
    counter = {
        dict(labels)["family"]: v
        for (_n, labels), v in metrics.counters_with_prefix(DISPATCH_METRIC).items()
    }
    return tracing.snapshot(), counter


@pytest.mark.parametrize("family", sorted(tracing._DISPATCH_PHASE))
def test_era_report_dispatch_is_the_counter(two_native_eras, family):
    """An era's dispatch seconds are differences of the one total: over
    both eras the pumps' `dispatch_s` sum to what the counter moved by,
    and the report puts exactly those seconds on the family's phase."""
    spans, counter = two_native_eras
    pumps = [s for s in spans if s["name"] == "engine.pump"]
    assert {s["args"]["era"] for s in pumps} == {1, 2}
    folded = sum(s["args"]["dispatch_s"].get(family, 0.0) for s in pumps)
    assert folded == pytest.approx(counter[family], rel=1e-9) and folded > 0
    stripped = [
        dict(s, args={k: v for k, v in s["args"].items() if k != "dispatch_s"})
        for s in spans
    ]
    phase = tracing._DISPATCH_PHASE[family]
    with_d = tracing.era_report(spans=spans, native=[])["eras"]
    without = tracing.era_report(spans=stripped, native=[])["eras"]
    assert [e["era"] for e in with_d] == [1, 2]
    moved = sum(
        a["phases_s"][phase] - b["phases_s"][phase]
        for a, b in zip(with_d, without)
    )
    # each era's figure is rounded to a microsecond, twice
    assert moved == pytest.approx(folded, abs=4e-6)


def test_recorder_off_moves_nothing():
    """At capacity 0 a native era leaves no span, no ring record and no
    dispatch second: the engine reads no clock."""
    from lachain_tpu.consensus.native_rt import DISPATCH_METRIC

    tracing.set_capacity(0)
    try:
        net = _quiesce_net()  # a whole era, pumped until the queue is empty
        assert net.delivered_count > 0
        assert net._lib.rt_trace_drain(net._h, None, 0) == 0
        assert net.trace_dropped() == 0
        net.close()
        assert tracing.snapshot() == [] and tracing.native_snapshot() == []
        assert not metrics.counters_with_prefix(DISPATCH_METRIC)
    finally:
        tracing.set_capacity(tracing.DEFAULT_CAPACITY)
