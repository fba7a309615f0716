"""The loop thread's ledger (utils/tracing.py account / ledger_begin /
ledger_end / loop_idle): exclusive seconds by part, a partition that closes
on a served node's thread, the consensus family of every protocol class, and
what the era report makes of it. Identities and counts; the one time read
here is the cost of a scope, against a generous ceiling."""
import ast
import asyncio
import importlib
import pathlib
import pkgutil
import threading
import time

import pytest

import lachain_tpu
import lachain_tpu.consensus
from lachain_tpu.consensus import messages as M
from lachain_tpu.consensus.protocol import Broadcaster, Protocol
from lachain_tpu.core.types import Transaction, sign_transaction
from lachain_tpu.crypto import ecdsa
from lachain_tpu.utils import metrics, tracing

N4 = 4
ERAS = 2
FAMILY_OF = {
    "ReliableBroadcast": "rbc",
    "BinaryAgreement": "ba",
    "BinaryBroadcast": "ba",
    "CommonCoin": "coin",
    "HoneyBadger": "tpke",
    "CommonSubset": "tpke",
    "RootProtocol": "commit",
}


def _ledger_counters():
    """{part or family: seconds} as the registry holds them."""
    out = {}
    for prefix in (tracing.LOOP_METRIC, tracing.DISPATCH_METRIC):
        for (_name, labels), secs in metrics.counters_with_prefix(prefix).items():
            out[labels[0][1]] = secs
    return out


def _union(ivs):
    total, end = 0.0, None
    for lo, hi in sorted(ivs):
        if end is None or lo > end:
            total, end = total + hi - lo, hi
        elif hi > end:
            total, end = total + hi - end, hi
    return total


@pytest.fixture(scope="module")
def fleet_run():
    """Four served nodes on this process's one loop, two eras back to back,
    twelve transfers submitted to node 0 while the first runs; the counters
    as they stood when the first era began and when the last ended."""
    from lachain_tpu.core.fleet import TcpFleet
    from lachain_tpu.rpc.service import RpcService

    priv = (36).to_bytes(32, "big")
    addr = ecdsa.address_from_public_key(ecdsa.public_key_bytes(priv))

    async def run():
        fleet = TcpFleet(
            n=N4, f=1, seed=36, txs_per_block=64, initial_balances={addr: 10**21}
        )
        tracing.reset_for_tests()
        metrics.reset_all_for_tests()
        await fleet.start()
        try:
            txs = [
                sign_transaction(
                    Transaction(
                        to=b"\x11" * 20, value=1, nonce=i, gas_price=1,
                        gas_limit=21000, invocation=b"",
                    ),
                    priv,
                    fleet.chain_id,
                )
                for i in range(12)
            ]

            async def submit():
                await asyncio.sleep(0.01)  # every node's era has begun
                assert all(fleet.nodes[0].submit_tx(stx) for stx in txs)

            before = _ledger_counters()
            await asyncio.gather(fleet.run_era(1), submit())
            for era in range(2, ERAS + 1):
                await fleet.run_era(era)
            after = _ledger_counters()
            # as an operator gets it from a live validator
            report = RpcService(fleet.nodes[0]).la_getEraReport()
        finally:
            await fleet.stop()
        return {
            "before": before,
            "after": after,
            "spans": tracing.snapshot(),
            "report": report,
            "frames": {
                labels[0][1]: n
                for (_n, labels), n in metrics.counters_with_prefix(
                    "network_frames_total"
                ).items()
            },
            "flush_wait": metrics.counter_value("network_flush_wait_seconds_total"),
            "scopes": metrics.counter_value(tracing.SCOPES_METRIC),
            "net_waits": metrics.histogram_snapshot("wait_seconds", {"resource": "net"}),
        }

    out = asyncio.run(run())
    tracing.reset_for_tests()
    metrics.reset_all_for_tests()
    return out


def _era_spans(run):
    return [s for s in run["spans"] if s["name"] == "era" and not s["open"]]


def test_partition_closes_on_the_fleets_thread(fleet_run):
    """idle + other + the named parts + the families = the time an era ran
    on the thread. The nodes share one loop, so their `era` spans overlap
    and the thread's time is their union."""
    moved = {
        p: fleet_run["after"][p] - fleet_run["before"].get(p, 0.0)
        for p in fleet_run["after"]
    }
    eras = _era_spans(fleet_run)
    assert len(eras) == N4 * ERAS
    ran = _union((s["start"], s["end"]) for s in eras)
    assert sum(moved.values()) == pytest.approx(ran, rel=0.02)
    # (`idle` has its own test: four nodes keep this loop busy)
    for part in ("other", "frame_in", "frame_verify", "frame_out",
                 "frame_sign", "frame_write", "journal", "exec"):
        assert moved.get(part, 0.0) > 0.0, part
    assert {f for f in tracing.DISPATCH_FAMILIES if moved.get(f, 0.0) > 0} == set(
        tracing.DISPATCH_FAMILIES
    )


def test_every_era_span_carries_a_split_that_sums_to_it(fleet_run):
    for s in _era_spans(fleet_run):
        split = {**s["args"]["loop_s"], **s["args"]["dispatch_s"]}
        assert set(s["args"]["dispatch_s"]) <= set(tracing.DISPATCH_FAMILIES)
        assert not set(s["args"]["loop_s"]) & set(tracing.DISPATCH_FAMILIES)
        assert sum(split.values()) == pytest.approx(s["end"] - s["start"], rel=0.01)


def test_admissions_and_gossip_are_charged_where_the_node_admits(fleet_run):
    # node 0 admitted twelve transfers and gossiped each while its era ran;
    # three peers admitted them from frames; every sender was recovered once
    for part in ("pool_admit", "gossip_out", "ecdsa_recover"):
        assert fleet_run["after"].get(part, 0.0) > 0.0, part


def test_frames_are_counted_and_wait_for_their_flush(fleet_run):
    frames = fleet_run["frames"]
    assert frames["in"] > 10 * ERAS and frames["out"] > 10 * ERAS
    # every frame written in this process is read in it
    assert abs(frames["in"] - frames["out"]) <= 2 * N4
    mean = fleet_run["flush_wait"] / frames["out"]
    assert 0.0 < mean < 1.0, "a frame waits for its worker's flush, not longer"
    assert fleet_run["scopes"] > 2 * (frames["in"] + frames["out"])


def test_no_span_is_held_open_around_a_connections_read(fleet_run):
    assert not [s for s in fleet_run["spans"] if s["name"] == "wait.net"]
    assert fleet_run["net_waits"] is None, "nor a sample a frame in wait_seconds"


def test_era_report_passes_the_split_through_and_the_table_prints_it(fleet_run):
    eras = fleet_run["report"]["eras"]
    assert [e["era"] for e in eras] == list(range(1, ERAS + 1))
    for ent in eras:
        assert ent["loop_s"]["other"] > 0 and ent["loop_s"]["frame_verify"] > 0
        assert set(ent["dispatch_s"]) == set(tracing.DISPATCH_FAMILIES)
        # one node's span, not the fleet's sum: the split fits the window
        total = sum(ent["loop_s"].values()) + sum(ent["dispatch_s"].values())
        assert total <= ent["wall_s"] * 1.01
    table = tracing.era_report_table(fleet_run["report"]).splitlines()
    second_rows = [ln for ln in table if "loop_s:" in ln]
    assert len(second_rows) == ERAS
    assert all("dispatch_s:" in ln and "frame_verify=" in ln for ln in second_rows)
    # an era whose span carries no ledger (the devnet's, an old trace) keeps
    # the table it had
    bare = {"eras": [dict(eras[0], loop_s={}, dispatch_s={})], "phases": tracing.PHASES}
    assert len(tracing.era_report_table(bare).splitlines()) == 3


def test_report_reads_the_loops_idle_as_its_net_bucket():
    """era.net_idle (category net) fills the `net` wait bucket where
    wait.net spans of the hub's readers used to; tracing.wait("net") stays
    an API and still does."""

    def span(name, cat, start, end, **args):
        return {"id": 0, "name": name, "cat": cat, "start": start, "end": end,
                "open": False, "args": args}

    era = span("era", "era", 10.0, 12.0, era=5)
    for idle in (
        span("era.net_idle", "net", 10.5, 11.0, era=5),
        span("wait.net", "wait", 10.5, 11.0, resource="net"),
    ):
        ent = tracing.era_report(spans=[era, idle], native=[])["eras"][0]
        assert ent["waits_s"]["net"] == pytest.approx(0.5)
        assert ent["idle_unattributed_s"] == pytest.approx(1.5)
        assert "loop_s" not in ent


class _Plain(Broadcaster):
    """Delivers a parent's request and a child's result in place, as the
    era router does: receive() re-enters receive()."""

    def __init__(self):
        self.protocols = {}

    def internal_request(self, req):
        self.protocols[req.to_id].receive(req)

    def internal_response(self, res):
        if res.to_id is not None:
            self.protocols[res.to_id].receive(res)


class _Parent(Protocol):
    family = "rbc"

    def handle_input(self, value):
        time.sleep(0.01)
        self.request("child", value)
        time.sleep(0.01)

    def handle_child_result(self, child_id, value):
        time.sleep(0.01)


class _Child(Protocol):
    family = "ba"

    def handle_input(self, value):
        time.sleep(0.04)
        self.emit_result(value)


def test_a_childs_seconds_are_not_its_parents():
    tracing.reset_for_tests()
    net = _Plain()
    net.protocols = {"parent": _Parent("parent", net), "child": _Child("child", net)}
    began = tracing.ledger_begin()
    t0 = time.monotonic()
    net.protocols["parent"].receive(M.Request(from_id=None, to_id="parent", input=1))
    wall = time.monotonic() - t0
    split = tracing.ledger_end(began)
    got = split["dispatch_s"]
    # parent: 10 ms + 10 ms around the child, and 10 ms handling its result
    # beneath the child's emit_result; the child keeps its own 40 ms only
    # (a sleep only ever overshoots, and more on a loaded machine)
    assert 0.039 < got["ba"] < 0.039 + 0.5 * wall
    assert 0.029 < got["rbc"] < 0.029 + 0.5 * wall
    assert got["rbc"] + got["ba"] == pytest.approx(wall, abs=0.005)
    assert set(got) == {"rbc", "ba"}
    assert split["loop_s"].get("other", 0.0) < 0.005, "nothing else ran"
    # outside every era a scope times nothing anybody reads
    net.protocols["parent"].receive(M.Request(from_id=None, to_id="parent", input=2))
    assert tracing.ledger_end(tracing.ledger_begin())["dispatch_s"] == {}


def _protocol_classes():
    for mod in pkgutil.iter_modules(lachain_tpu.consensus.__path__):
        importlib.import_module(f"lachain_tpu.consensus.{mod.name}")
    importlib.import_module("lachain_tpu.core.node")  # whatever else subclasses it

    seen, todo = [], list(Protocol.__subclasses__())
    while todo:
        cls = todo.pop()
        todo += cls.__subclasses__()
        if cls.__module__.startswith("lachain_tpu.") and cls not in seen:
            seen.append(cls)
    return sorted(seen, key=lambda c: c.__name__)


@pytest.mark.parametrize("cls", _protocol_classes(), ids=lambda c: c.__name__)
def test_every_protocol_names_its_family(cls):
    # a protocol added without a family fails here, not in a ledger
    assert cls.__name__ in FAMILY_OF, f"{cls.__name__}: which family is it?"
    assert cls.family == FAMILY_OF[cls.__name__]
    assert cls.family in tracing.DISPATCH_FAMILIES
    assert "family" in vars(cls), "named on the class, not inherited"


def test_all_seven_protocols_are_found():
    assert {c.__name__ for c in _protocol_classes()} == set(FAMILY_OF)
    assert Protocol.family == "other"


def test_a_scope_on_another_thread_stays_out_of_the_loops_partition():
    tracing.reset_for_tests()
    metrics.reset_all_for_tests()

    def elsewhere():
        with tracing.account("frame_verify"):
            time.sleep(0.02)

    async def era():
        sid = tracing.begin("era", era=1)
        began = tracing.ledger_begin()
        worker = threading.Thread(target=elsewhere)
        worker.start()
        with tracing.account("frame_in"):
            time.sleep(0.01)
        with tracing.loop_idle("era.net_idle", cat="net", era=1):
            await asyncio.sleep(0.03)
        worker.join()
        split = tracing.ledger_end(began)
        tracing.end(sid, **split)
        return split

    split = asyncio.run(era())
    assert "frame_verify" not in split["loop_s"]
    assert 0.009 < split["loop_s"]["frame_in"] < 0.1
    assert 0.02 < split["loop_s"]["idle"] < 0.3
    assert set(split["loop_s"]) == {"frame_in", "idle", "other"}
    assert split["dispatch_s"] == {}
    assert "frame_verify" not in _ledger_counters()
    assert _ledger_counters()["idle"] == pytest.approx(split["loop_s"]["idle"], abs=1e-5)
    spans = tracing.snapshot()
    parked = sum(s["end"] - s["start"] for s in spans if s["name"] == "era.net_idle")
    assert parked == pytest.approx(split["loop_s"]["idle"], abs=5e-3)
    (span,) = [s for s in spans if s["name"] == "era"]
    assert sum(span["args"]["loop_s"].values()) == pytest.approx(
        span["end"] - span["start"], rel=0.01
    )


def test_capacity_zero_moves_no_counter():
    tracing.reset_for_tests()
    metrics.reset_all_for_tests()
    tracing.set_capacity(0)
    try:
        assert tracing.account("frame_in") is tracing.account("exec")
        assert tracing.ledger_begin() is None and tracing.ledger_end(None) == {}

        async def era():
            began = tracing.ledger_begin()
            with tracing.account("frame_in"):
                time.sleep(0.002)
            net = _Plain()
            net.protocols = {"p": _Parent("p", net), "child": _Child("child", net)}
            net.protocols["p"].receive(M.Request(from_id=None, to_id="p", input=1))
            with tracing.loop_idle("era.net_idle", cat="net"):
                await asyncio.sleep(0.01)
            return tracing.ledger_end(began)

        assert asyncio.run(era()) == {}
        assert _ledger_counters() == {}
        assert metrics.counter_value(tracing.SCOPES_METRIC) == 0
    finally:
        tracing.set_capacity(tracing.DEFAULT_CAPACITY)
    began = tracing.ledger_begin()
    with tracing.account("frame_in"):
        pass
    assert "frame_in" in tracing.ledger_end(began)["loop_s"]
    assert "frame_in" in _ledger_counters()


def _awaits_inside_account_scopes():
    """(file, line) of every `with tracing.account(...)` in the package
    whose body suspends."""
    found, scopes = [], 0
    root = pathlib.Path(lachain_tpu.__file__).parent
    for path in sorted(root.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text(encoding="utf-8"))):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            calls = [
                item.context_expr for item in node.items
                if isinstance(item.context_expr, ast.Call)
                and isinstance(item.context_expr.func, ast.Attribute)
                and item.context_expr.func.attr == "account"
            ]
            if not calls:
                continue
            scopes += 1
            suspends = isinstance(node, ast.AsyncWith) or any(
                isinstance(inner, (ast.Await, ast.Yield, ast.YieldFrom,
                                   ast.AsyncFor, ast.AsyncWith))
                for stmt in node.body
                for inner in ast.walk(stmt)
            )
            if suspends:
                found.append((str(path.relative_to(root)), node.lineno))
    return found, scopes


def test_no_scope_spans_an_await():
    """A scope held across a suspension would bill whatever the loop runs
    meanwhile to its part."""
    found, scopes = _awaits_inside_account_scopes()
    assert scopes >= 14, "the call sites the ledger is made of"
    assert found == []


def test_a_scope_costs_well_under_five_microseconds():
    """The best of many short rounds: a round a busy host preempts is slow,
    one it leaves alone says what a scope costs."""
    tracing.reset_for_tests()
    n = 1_000
    best = float("inf")
    for _ in range(300):
        t0 = time.perf_counter()
        for _ in range(n):
            with tracing.account("frame_in"):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    print(f"tracing.account: {best * 1e9:.0f} ns a scope")
    assert best < 5e-6
