"""A rolling restart at N=4 on this process's one loop: four served nodes on
LsmKV under tmp_path in `Node.run()`, a steady stream of transfers into
node 0, and twice in a row (two victims) one validator dropped as a kill -9
drops it and rebuilt on the same store directory and port.

Dropped as a kill drops it: its tasks die and its sockets close, and the
store is left exactly as it stands: no flush, no close, no `Node.stop()`
(whose workers would send a last frame). The abandoned engine handle stays
open in this process as a dead process's would not, but nothing writes
through it again; the rebuilt node opens the directory afresh and replays
the WAL, which is what `perfbench/drivers/peers_roll.py` does with a real
SIGKILL in seven processes on the chip's host.

Held here (the numbers are the configuration hb7-roll's guarantees): (1)
equal block hashes at every height in all four stores, (2) every committed
transaction, its receipt, balances and nonces read back from all four, (4) a
reopened store passes fsck and holds the chain's blocks, (5) nobody holds
evidence of equivocation, the restarted node finished an era BY CONSENSUS
after each restart, and every block executes again from genesis on a fresh
MemoryKV to its header's state root. Then the spans and counters of a
restart: once a restart or once a synced block, never once a transaction.
"""
import asyncio
import time

import pytest

from lachain_tpu.consensus.keys import trusted_key_gen
from lachain_tpu.core.execution import get_balance, get_nonce
from lachain_tpu.core.node import Node
from lachain_tpu.core.types import Transaction, sign_transaction
from lachain_tpu.crypto import ecdsa
from lachain_tpu.storage.lsm import LsmKV
from lachain_tpu.utils import metrics, tracing
from perfbench import reference

N, F, CHAIN = 4, 1, 225
VICTIMS = (2, 3)
SENDERS = 8
COUNTERS = (
    "sync_blocks_applied_total",
    "sync_blocks_served_total",
    "sync_requests_total",
    "network_peer_reconnects_total",
    "network_backoff_seconds_total",
    "consensus_ba_rounds_total",
    "consensus_acs_slots_rejected_total",
)


class _Rng:
    def __init__(self, seed):
        import random

        self._r = random.Random(seed)

    def randbelow(self, n):
        return self._r.randrange(n)


async def _crash(node, run_task) -> None:
    """What SIGKILL does to a process, less the process: the era loop and
    every task gone, every socket closed with nothing more sent, the store
    untouched."""
    run_task.cancel()
    await asyncio.gather(run_task, return_exceptions=True)
    node._stopping = True
    node._watchdog_task.cancel()
    for worker in node.network._workers.values():
        worker._task.cancel()  # no final flush: a dead process sends nothing
    await asyncio.gather(
        *(w._task for w in node.network._workers.values()), return_exceptions=True
    )
    await node.synchronizer.stop()
    await node.network.hub.stop()


async def _until(done, timeout, what):
    deadline = time.monotonic() + timeout
    while not done():
        if time.monotonic() > deadline:
            raise TimeoutError(what)
        await asyncio.sleep(0.02)


@pytest.fixture(scope="module")
def rolled(tmp_path_factory):
    """The scenario, once; every test below reads what it left."""
    root = tmp_path_factory.mktemp("roll")
    pub, privs = trusted_key_gen(N, F, rng=_Rng(38))
    keys = [ecdsa.generate_private_key(_Rng(3800 + i)) for i in range(SENDERS)]
    senders = [ecdsa.address_from_public_key(ecdsa.public_key_bytes(k)) for k in keys]
    balances = {a: 10**21 for a in senders}
    recipient = bytes(range(20))
    capacity = tracing.capacity()
    tracing.set_capacity(1 << 17)
    before = {c: metrics.counter_value(c) for c in COUNTERS}
    began = time.monotonic()  # the ring and the registry are the process's
    out = {"pub": pub, "balances": balances, "restarts": []}

    def make(i, port=0):
        kv = LsmKV(str(root / f"validator{i}.db"))
        node = Node(
            index=i, public_keys=pub, private_keys=privs[i], chain_id=CHAIN, kv=kv,
            port=port, initial_balances=balances, flush_interval=0.01, txs_per_block=100,
        )
        return node, kv

    async def scenario():
        nodes, kvs = map(list, zip(*(make(i) for i in range(N))))
        abandoned = []
        for node in nodes:
            await node.start()
        addresses = [node.address for node in nodes]
        for i, node in enumerate(nodes):
            node.connect([a for j, a in enumerate(addresses) if j != i])
        runs = [asyncio.ensure_future(node.run(first_era=1)) for node in nodes]
        sent = {}
        loading = True

        async def load():
            k = 0
            while loading:
                stx = sign_transaction(
                    Transaction(to=recipient, value=1, nonce=k // SENDERS,
                                gas_price=1, gas_limit=21000),
                    keys[k % SENDERS], CHAIN,
                )
                assert nodes[0].submit_tx(stx)
                sent[stx.hash()] = stx
                k += 1
                await asyncio.sleep(0.01)

        loader = asyncio.ensure_future(load())
        height = nodes[0].block_manager.current_height
        try:
            await _until(lambda: height() >= 3, 30, "the first three blocks")
            for victim in VICTIMS:
                port = nodes[victim].address.port
                died_at = nodes[victim].block_manager.current_height()
                await _crash(nodes[victim], runs[victim])
                abandoned.append(kvs[victim])
                # the chain goes on without it
                await _until(lambda: height() >= died_at + 3, 30, "three blocks with one out")
                node, kv = make(victim, port)
                held = node.block_manager.current_height()
                out["restarts"].append(
                    {
                        "victim": victim,
                        "fsck": node.fsck_report,
                        "held": held,
                        "opened_with": kv.opened_with,
                        # guarantee (4), before it connects
                        "on_chain": all(
                            node.block_manager.block_by_height(h).hash()
                            == nodes[0].block_manager.block_by_height(h).hash()
                            for h in range(1, held + 1)
                        ),
                        "clock": node.recovery,
                    }
                )
                nodes[victim], kvs[victim] = node, kv
                await node.start(first_era=held + 1)
                assert node.address.port == port
                node.connect([a for j, a in enumerate(addresses) if j != victim])
                runs[victim] = asyncio.ensure_future(node.run(first_era=held + 1))
                await _until(lambda: node.recovery.done, 30, f"validator {victim} back")
            loading = False
            await loader
            held_by_0 = lambda: {
                h
                for b in range(1, height() + 1)
                for h in nodes[0].block_manager.block_by_height(b).tx_hashes
            }
            await _until(lambda: set(sent) <= held_by_0(), 30, "every transfer in a block")
            top = height()
            await _until(
                lambda: all(n.block_manager.current_height() >= top for n in nodes),
                30, "all four at one height",
            )
        finally:
            loading = False
            for task in runs + [loader]:
                task.cancel()
            await asyncio.gather(*runs, loader, return_exceptions=True)
            for node in nodes:
                await node.stop()
        out.update(nodes=nodes, kvs=kvs, sent=sent, top=top, recipient=recipient,
                   senders=senders)
        return abandoned

    abandoned = asyncio.run(scenario())
    out["spans"] = [s for s in tracing.snapshot() if s["start"] >= began]
    out["counters"] = {c: metrics.counter_value(c) - before[c] for c in COUNTERS}
    yield out
    tracing.set_capacity(capacity)
    for kv in out["kvs"] + abandoned:
        kv.close()


def _blocks(node, top):
    return [node.block_manager.block_by_height(h) for h in range(1, top + 1)]


def test_equal_block_hashes_in_all_four_stores(rolled):
    chains = [[b.hash() for b in _blocks(n, rolled["top"])] for n in rolled["nodes"]]
    assert rolled["top"] >= 9 and all(chain == chains[0] for chain in chains)


def test_every_transfer_reads_back_from_all_four_stores(rolled):
    sent, top = rolled["sent"], rolled["top"]
    assert len(sent) > 3 * top, "a block holds several transfers: a span a block is no span a transfer"
    nonces = {s: 0 for s in rolled["senders"]}
    for stx in sent.values():
        nonces[stx.sender(CHAIN)] += 1
    for node in rolled["nodes"]:
        committed = [h for b in _blocks(node, top) for h in b.tx_hashes]
        assert sorted(committed) == sorted(sent), "each transfer in exactly one block"
        bm = node.block_manager
        assert all(bm.transaction_by_hash(h) is not None for h in committed)
        assert all(bm.receipt_by_hash(h) is not None for h in committed)
        snap = node.state.new_snapshot(node.state.roots_at(top))
        assert get_balance(snap, rolled["recipient"]) == len(sent)
        assert {s: get_nonce(snap, s) for s in nonces} == nonces


def test_a_reopened_store_passes_fsck_and_is_on_the_chain(rolled):
    assert [r["victim"] for r in rolled["restarts"]] == list(VICTIMS)
    for r in rolled["restarts"]:
        assert not r["fsck"].fatal
        assert r["held"] >= 3 and r["on_chain"]
        # what the engine's open found: the WAL replayed, no torn tail here
        assert r["opened_with"]["wal_records"] > 0
        assert r["opened_with"]["repaired"] == 0


def test_nobody_holds_evidence_of_equivocation(rolled):
    assert [len(node.evidence) for node in rolled["nodes"]] == [0] * N


def test_each_restarted_node_finished_an_era_by_consensus(rolled):
    for r in rolled["restarts"]:
        clock = r["clock"]
        assert clock.done and clock.rejoined_era > r["held"]
        report = clock.report()
        assert report["height_at_open"] == r["held"]
        # the phases follow one another without a gap
        total = report["marks"]["rejoin"] - report["marks"]["listening"]
        assert report["connect_s"] + report["catch_up_s"] + report["rejoin_s"] == pytest.approx(total)
        assert report["blocks"] >= 1, "the chain moved on while it was out"
    eras = [s for s in rolled["spans"] if s["name"] == "era" and not s["open"]]
    assert {s["args"]["outcome"] for s in eras} >= {"consensus", "synced"}


def test_every_block_executes_again_from_genesis_to_its_state_root(rolled):
    node = rolled["nodes"][VICTIMS[-1]]  # read from a restarted validator's store
    chain = [
        (block, [rolled["sent"][h] for h in block.tx_hashes])
        for block in _blocks(node, rolled["top"])
    ]
    assert reference.reexecute(
        CHAIN, rolled["balances"], rolled["pub"].ecdsa_pub_keys, chain
    ) == []


RESTARTS = len(VICTIMS)


@pytest.mark.parametrize(
    "name, count",
    [
        ("lsm.open", N + RESTARTS),
        ("node.recover.pool", RESTARTS),
        ("node.recover.journal", RESTARTS),
        ("node.recover.connect", RESTARTS),
        ("node.recover.catch_up", RESTARTS),
        ("node.recover.rejoin", RESTARTS),
        ("sync.apply", None),
    ],
)
def test_spans_of_a_restart(rolled, name, count):
    spans = [s for s in rolled["spans"] if s["name"] == name and not s["open"]]
    if count is None:  # one a synced block, never one a transaction
        applied = rolled["counters"]["sync_blocks_applied_total"]
        assert applied <= len(spans) <= rolled["top"] * N
        assert all("height" in s["args"] for s in spans)
    else:
        assert len(spans) == count
    if name == "lsm.open":
        assert all({"wal_records", "repaired"} <= set(s["args"]) for s in spans)
    if name == "node.recover.catch_up":
        assert sum(s["args"]["blocks"] for s in spans) == sum(
            r["clock"].blocks for r in rolled["restarts"]
        )


@pytest.mark.parametrize("name", COUNTERS)
def test_counters_of_a_restart(rolled, name):
    value, top = rolled["counters"][name], rolled["top"]
    assert value > 0
    if name == "sync_blocks_applied_total":
        assert value >= sum(r["clock"].blocks for r in rolled["restarts"])
        assert value <= top * N
    elif name == "sync_blocks_served_total":
        assert value >= rolled["counters"]["sync_blocks_applied_total"]
    elif name == "network_peer_reconnects_total":
        # at most one a peer's worker a restart
        assert value <= RESTARTS * (N - 1)
    elif name == "consensus_ba_rounds_total":
        # every BA of every era decides in at least one round, in each node
        assert value >= top * N
    elif name == "consensus_acs_slots_rejected_total":
        assert value < top * N * N
    assert value < len(rolled["sent"]) * N * N, "nothing here counts transactions"
