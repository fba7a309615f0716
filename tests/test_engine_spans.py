"""The native engine's host time, named: a span at every rt_run call
(`engine.pump`) and at every engine->Python callback (`cross.<op>`), the
`era.advance` span, the dispatch-seconds counter read from the engine's
running totals, and the recorder's off state. N=4 native devnet on the CPU:
counts and nesting only — a CPU run says nothing about time.
"""
import ctypes
import gc
import os
import struct
import subprocess
import sys
import threading

import pytest

from lachain_tpu.consensus.native_rt import CROSSINGS_METRIC, DISPATCH_METRIC
from lachain_tpu.core.devnet import Devnet
from lachain_tpu.utils import metrics, tracing

pytestmark = pytest.mark.observability

FAMILIES = ("rbc", "ba", "coin", "tpke", "commit", "other")
# callbacks that run inside deliver() and are not subtracted from the
# engine's dispatch time; with every protocol native-owned they do not occur
LEGACY_OPS = ("opaque_message", "acs_result", "coin_request")
# what a callback may run inside, on the one thread that drives the engine
CROSS_PARENTS = (
    "engine.pump",
    "consensus.propose",
    "tpke.flush",
    "rbc.flush",
    "rbc.fanout",
    "hb.apply_era_results",
    "era.advance",
)


@pytest.fixture(autouse=True)
def _clean():
    tracing.reset_for_tests()
    metrics.reset_all_for_tests()
    yield
    tracing.reset_for_tests()
    metrics.reset_all_for_tests()


def _devnet(**kw) -> Devnet:
    return Devnet(4, 1, seed=9, txs_per_block=20, engine="native", **kw)


def _crossings() -> dict:
    return {
        dict(labels)["op"]: int(v)
        for (_n, labels), v in metrics.counters_with_prefix(CROSSINGS_METRIC).items()
    }


def _dispatch() -> dict:
    return {
        fam: metrics.counter_value(DISPATCH_METRIC, labels={"family": fam})
        for fam in FAMILIES
    }


def _cross_counts(spans) -> dict:
    out = {}
    for s in spans:
        if s["name"].startswith("cross."):
            assert s["cat"] == "engine" and not s["open"]
            op = s["name"][len("cross."):]
            out[op] = out.get(op, 0) + 1
    return out


def _inside(s, outer) -> bool:
    return outer["start"] <= s["start"] and s["end"] <= outer["end"]


@pytest.mark.parametrize("rbc_batch", [False, True])
def test_one_cross_span_for_every_counted_callback(rbc_batch):
    net = _devnet(rbc_batch=rbc_batch)
    try:
        net.run_eras(1, 2)
    finally:
        net.close()
    counted = _crossings()
    assert counted and _cross_counts(tracing.snapshot()) == counted
    assert counted["root_produce"] == 2 * 4
    assert ("rbc_need" in counted) == rbc_batch


# the callbacks an N=4 era with batched RBC makes through Engine::cross
ERA_OPS = (
    "root_input", "root_sign", "root_verify", "root_produce",
    "hb_acs", "coin_sign", "coin_combine", "rbc_need",
)


def _ring_kinds(net) -> dict:
    """Record kinds in the engines' rings, read raw (the drain consumes)."""
    kinds = {}
    lib = net.net._lib
    for h in net.net._live_engines():
        need = lib.rt_trace_drain(h, None, 0)
        buf = (ctypes.c_uint8 * (need + 4096))()
        got = lib.rt_trace_drain(h, buf, len(buf))
        for i in range(0, got, 32):
            kind = struct.unpack_from(">I", buf, i + 16)[0]
            kinds[kind] = kinds.get(kind, 0) + 1
    return kinds


@pytest.fixture(scope="module")
def two_eras():
    tracing.reset_for_tests()
    metrics.reset_all_for_tests()
    net = _devnet(rbc_batch=True)
    try:
        net.run_eras(1, 2)
        return _cross_counts(tracing.snapshot()), _crossings(), _ring_kinds(net)
    finally:
        net.close()


@pytest.mark.parametrize("op", ERA_OPS)
def test_each_crossing_is_timed_once(two_eras, op):
    """A callback has one timing, the span cross.<op>: as many spans as
    the counter counted, and no record of it (kind 2) or of per-era
    dispatch seconds (kind 5) in the engine's ring."""
    spans, counted, ring = two_eras
    assert spans[op] == counted[op] > 0
    assert ring and not {2, 5} & set(ring), ring


def test_engine_spans_nest_in_their_era_and_in_a_named_parent():
    net = _devnet(rbc_batch=True)
    try:
        net.run_eras(1, 2)
    finally:
        net.close()
    spans = tracing.snapshot()
    eras = {s["args"]["era"]: s for s in spans if s["name"] == "era"}
    assert sorted(eras) == [1, 2]
    engine = [s for s in spans if s["cat"] == "engine"]
    assert {s["name"].split(".")[0] for s in engine} == {"engine", "cross", "era", "rbc"}
    for s in engine:
        assert _inside(s, eras[s["args"]["era"]]), s
    parents = [s for s in spans if s["name"] in CROSS_PARENTS]
    for s in engine:
        if s["name"].startswith("cross."):
            assert s["args"]["vid"] in range(4)
            assert any(_inside(s, p) for p in parents), s
    pumps = [s for s in spans if s["name"] == "engine.pump"]
    assert all(s["args"]["processed"] >= 0 for s in pumps)
    assert sum(s["args"]["processed"] for s in pumps) == net.net.delivered_count


def test_every_era_holds_one_advance_and_at_least_one_pump():
    net = _devnet()
    try:
        net.run_eras(1, 3)
    finally:
        net.close()
    spans = tracing.snapshot()
    eras = [s for s in spans if s["name"] == "era"]
    assert len(eras) == 3
    for era in eras:
        held = [s for s in spans if s is not era and _inside(s, era)]
        assert sum(s["name"] == "era.advance" for s in held) == 1
        assert sum(s["name"] == "engine.pump" for s in held) >= 1


@pytest.mark.parametrize("engine_ring", [None, 8])
def test_dispatch_counter_reads_the_engines_totals_not_its_ring(engine_ring):
    """The counter grows each era, stays under the time the engine was
    pumped, and a ring too small for an era's records changes nothing."""
    net = _devnet(rbc_batch=True)
    if engine_ring is not None:
        net.net.trace_configure(engine_ring)
    try:
        before = _dispatch()
        assert not any(before.values())
        for era in (1, 2):
            net.run_era(era)
            after = _dispatch()
            # every family of a full era: RBC, BA, coin shares, decryption
            # shares, signed headers
            for fam in ("rbc", "ba", "coin", "tpke", "commit"):
                assert after[fam] > before[fam], (era, fam)
            before = after
        if engine_ring is not None:
            assert net.net.trace_dropped() > 0, "the ring did overflow"
    finally:
        net.close()
    spans = tracing.snapshot()
    pumped = sum(s["end"] - s["start"] for s in spans if s["name"] == "engine.pump")
    assert 0 < sum(before.values()) <= pumped
    counted = _crossings()
    assert not set(counted) & set(LEGACY_OPS)
    assert _cross_counts(spans) == counted


def test_capacity_zero_is_off_and_changes_no_block():
    def hashes():
        net = _devnet(rbc_batch=True)
        try:
            return [b.hash() for b in net.run_eras(1, 2)]
        finally:
            net.close()

    recorded = hashes()
    assert tracing.snapshot() and sum(_dispatch().values()) > 0
    metrics.reset_all_for_tests()
    tracing.set_capacity(0)
    try:
        assert tracing.capacity() == 0
        assert hashes() == recorded
        with tracing.span("era", era=1) as sid, tracing.wait("net"):
            tracing.annotate(sid, note=1)
            tracing.instant("tick")
        assert tracing.begin("x") == 0
        assert tracing.snapshot() == [] and tracing.native_snapshot() == []
        assert tracing.open_spans() == []
        assert tracing.dropped_total() == 0
        assert not metrics.counters_with_prefix("trace_events_dropped_total")
        assert sum(_dispatch().values()) == 0
        assert sum(_crossings().values()) > 0, "the callbacks are still counted"
    finally:
        tracing.set_capacity(tracing.DEFAULT_CAPACITY)
    tracing.instant("tick")
    assert [s["name"] for s in tracing.snapshot()] == ["tick"]


def test_environment_capacity_zero_starts_the_process_off():
    """LACHAIN_TRACE_CAPACITY=0 is how a benchmark run measures what the
    recorder costs: the process starts off, engines included."""
    script = (
        "from lachain_tpu.core.devnet import Devnet\n"
        "from lachain_tpu.utils import metrics, tracing\n"
        "net = Devnet(4, 1, seed=9, txs_per_block=20, engine='native', rbc_batch=True)\n"
        "net.run_era(1)\n"
        "print(tracing.capacity(), net.net._trace_capacity, len(tracing.snapshot()),\n"
        "      len(tracing.native_snapshot()), tracing.dropped_total(),\n"
        "      len(metrics.counters_with_prefix('consensus_engine_dispatch')),\n"
        "      len(metrics.counters_with_prefix('trace_events_dropped')))\n"
        "net.close()\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", script], cwd=root, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, LACHAIN_TRACE_CAPACITY="0", LACHAIN_TPU_BACKEND="native"),
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == ["0"] * 7


def test_set_capacity_resizes_the_engines_that_are_registered():
    net = _devnet()
    try:
        assert net.net._trace_capacity == tracing.capacity() == tracing.DEFAULT_CAPACITY
        net.run_era(1)
        grown = sum(_dispatch().values())
        assert grown > 0
        tracing.set_capacity(0)
        assert net.net._trace_capacity == 0
        net.run_era(2)
        assert sum(_dispatch().values()) == grown, "off: the engine reads no clock"
        tracing.set_capacity(1 << 15)
        assert net.net._trace_capacity == 1 << 15
        late = _devnet()
        try:
            assert late.net._trace_capacity == 1 << 15, "built later, same size"
        finally:
            late.close()
        net.run_era(3)
        assert sum(_dispatch().values()) > grown
        # what the rings held before a resize was drained, not lost
        assert any(e["name"] == "post:root_header" for e in tracing.native_snapshot())
    finally:
        net.close()
        tracing.set_capacity(tracing.DEFAULT_CAPACITY)


def test_pipelined_eras_fold_each_engines_totals_once():
    net = _devnet(pipeline_window=1)
    try:
        net.run_eras(1, 3)
        assert set(net.net._phase_seen) <= set(net.net._live_engines())
    finally:
        net.close()
    spans = tracing.snapshot()
    assert _cross_counts(spans) == _crossings()
    pumped = sum(s["end"] - s["start"] for s in spans if s["name"] == "engine.pump")
    assert 0 < sum(_dispatch().values()) <= pumped
    assert {s["args"]["era"] for s in spans if s["name"] == "engine.pump"} == {1, 2, 3}


def test_devnet_close_closes_the_engine():
    net = _devnet()
    net.run_era(1)
    assert net.net._h is not None
    net.close()
    assert net.net._h is None and not net.net._era_engines
    net.close()  # and again: nothing left to close
    python_net = Devnet(4, 1, seed=9, txs_per_block=20, engine="python")
    python_net.close()  # the simulator has nothing to close


def test_snapshot_does_not_deadlock_on_a_collected_engine():
    """snapshot() used to build its dicts under the tracer's lock; a
    collector run in there finalised a dropped NativeSimulatedNetwork,
    whose __del__ drains into the tracer under the same lock."""
    finished = threading.Event()

    def worker():
        gc.disable()
        try:
            net = _devnet()
            net.run_era(1)
            for i in range(2000):
                tracing.instant("tick", i=i)
            # engine and routers hold each other: only the collector frees it
            del net
            gc.set_threshold(1)
            gc.enable()
            assert len(tracing.snapshot()) >= 2000
        finally:
            gc.set_threshold(700, 10, 10)
            gc.enable()
        finished.set()

    thresholds = gc.get_threshold()
    thread = threading.Thread(target=worker, daemon=True)
    thread.start()
    thread.join(20)
    gc.set_threshold(*thresholds)
    assert finished.is_set(), "snapshot() is stuck behind the engine's __del__"
