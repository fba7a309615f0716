"""An admission's repository row with the wait at the frame (core/tx_pool.py).

A pool whose owner took `frame_barrier()` on a store with an overlapping WAL
submits an admitted transaction's row (`write_batch_async`) and waits for
its fsync where the admission is acknowledged: in front of every write to a
socket (network/worker.py `durable_before_wire`) and of the RPC answer
(rpc/service.py `_after_pool_barrier`). These tests hold the guarantee under
that timing: nothing that names an admitted transaction leaves the process
before its row is durable; a crash between the submit and the barrier leaves
a prefix of the submitted rows and a clean store; a pool nobody took the
barrier of, or one on a store without such a WAL, issues the very calls it
always issued.
"""
import asyncio
import json
import os
import random
import signal
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from lachain_tpu.consensus.keys import trusted_key_gen
from lachain_tpu.core.tx_pool import TransactionPool
from lachain_tpu.core.types import Transaction, sign_transaction
from lachain_tpu.crypto import ecdsa
from lachain_tpu.network import wire
from lachain_tpu.network.worker import ClientWorker
from lachain_tpu.storage.fsck import fsck
from lachain_tpu.storage.kv import EntryPrefix, MemoryKV, SqliteKV, prefixed
from lachain_tpu.storage.lsm import LsmKV
from lachain_tpu.utils import metrics, tracing
from test_journal_barrier import WalKV as _JournalWalKV

pytestmark = pytest.mark.crash

CHAIN = 225
ROWS = "txpool_admit_rows_total"
WAITS = "txpool_admit_waits_total"
SECONDS = "txpool_admit_store_seconds_total"
_POOL = prefixed(EntryPrefix.POOL_TX)


class Rng:
    def __init__(self, seed=1):
        self._r = random.Random(seed)

    def randbelow(self, n):
        return self._r.randrange(n)


def _keys(n, seed):
    return [ecdsa.generate_private_key(Rng(seed + i)) for i in range(n)]


def _signed(priv, nonce, gas_price=1, value=1):
    tx = Transaction(
        to=b"\x09" * 20, value=value, nonce=nonce, gas_price=gas_price,
        gas_limit=21000,
    )
    return sign_transaction(tx, priv, CHAIN)


def _row(stx):
    return prefixed(EntryPrefix.POOL_TX, stx.hash())


def _spy(base):
    """`base` with every call the pool's repository can receive recorded."""

    class Spy(base):
        def __init__(self, *args, **kwargs):
            self.calls = []
            super().__init__(*args, **kwargs)

        def put(self, key, value):
            self.calls.append(("put", key))
            # LsmKV's put is a one-op write_batch: recorded once, as a put
            self.calls, keep = [], self.calls
            try:
                super().put(key, value)
            finally:
                self.calls = keep

        def write_batch(self, puts, deletes=()):
            self.calls.append(("write_batch", [k for k, _ in puts], list(deletes)))
            super().write_batch(puts, deletes)

        def write_batch_async(self, puts, deletes=()):
            self.calls.append(
                ("write_batch_async", [k for k, _ in puts], list(deletes))
            )
            self.calls, keep = [], self.calls
            try:
                return super().write_batch_async(puts, deletes)
            finally:
                self.calls = keep

        def write_barrier(self, ticket):
            self.calls.append(("write_barrier", ticket))
            super().write_barrier(ticket)

    return Spy


class WalKV(_JournalWalKV):
    """The journal tests' double of a KV with an overlapping WAL (an async
    batch is durable only after a barrier for its ticket, or after a later
    synchronous batch), with a WAL that can refuse to fsync."""

    def __init__(self):
        super().__init__()
        self.fail_barriers = 0

    def write_barrier(self, ticket):
        if self.fail_barriers:
            self.fail_barriers -= 1
            self.barrier_calls.append(ticket)
            raise IOError("the WAL cannot fsync")
        super().write_barrier(ticket)

    def undurable_pool_rows(self):
        return {
            k
            for keys in self._undurable.values()
            for k in keys
            if k.startswith(_POOL)
        }


def _counters():
    return tuple(metrics.counter_value(n) for n in (ROWS, WAITS, SECONDS))


# -- (a) submit, barrier, reopen ---------------------------------------------


def test_add_submits_and_the_barrier_waits_once(tmp_path):
    """The owner took the frame barrier on LsmKV: `add` returns with a
    ticket pending and no wait, one `barrier()` clears it for every row so
    far, a second makes no call into the KV, and a reopened store restores
    every row."""
    path = str(tmp_path / "pool")
    kv = _spy(LsmKV)(path)
    pool = TransactionPool(kv, CHAIN, lambda a: 0)
    hook = pool.frame_barrier()
    assert hook == pool.barrier and not pool.rows_pending()
    hook()
    assert kv.calls == [], "a barrier with nothing submitted reaches no KV"
    keys = _keys(6, 100)
    txs = [_signed(k, nonce) for k in keys for nonce in range(3)]
    rows0, waits0, secs0 = _counters()
    tracing.reset_for_tests()
    try:
        assert all(pool.add(stx) for stx in txs)
        assert [c[0] for c in kv.calls] == ["write_batch_async"] * len(txs)
        assert [c[1] for c in kv.calls] == [[_row(stx)] for stx in txs]
        assert pool.rows_pending(), "add keeps the ticket and does not wait"
        assert not [s for s in tracing.snapshot() if s["name"] == "pool.barrier"]
        kv.calls.clear()
        hook()
        assert [c[0] for c in kv.calls] == ["write_barrier"], "one wait"
        assert not pool.rows_pending()
        hook()
        hook()
        assert len(kv.calls) == 1, "nothing pending: no call into the KV"
        spans = [s for s in tracing.snapshot() if s["name"] == "pool.barrier"]
        assert len(spans) == 1 and spans[0]["args"]["rows"] == len(txs)
        assert spans[0]["cat"] == "pool"
    finally:
        tracing.reset_for_tests()
    rows1, waits1, secs1 = _counters()
    assert rows1 - rows0 == len(txs) and waits1 - waits0 == 1
    assert secs1 > secs0
    # a later admission is a new ticket and a new wait
    late = _signed(keys[0], 3)
    assert pool.add(late) and pool.rows_pending()
    hook()
    assert not pool.rows_pending()
    kv.close()
    kv2 = LsmKV(path)
    try:
        pool2 = TransactionPool(kv2, CHAIN, lambda a: 0)
        assert pool2.restore() == len(txs) + 1
        assert pool2.tx_hashes() == {stx.hash() for stx in txs + [late]}
    finally:
        kv2.close()


# -- (b) order in the WAL: a put before any later delete of its key ----------


def test_a_submitted_row_is_ahead_of_its_delete_and_a_replacement_is_one_batch(
    tmp_path,
):
    path = str(tmp_path / "pool")
    kv = _spy(LsmKV)(path)
    state = {}
    pool = TransactionPool(kv, CHAIN, lambda a: state.get(a, 0))
    pool.frame_barrier()
    (a, b) = _keys(2, 200)
    addr_a = ecdsa.address_from_public_key(ecdsa.public_key_bytes(a))
    included = _signed(a, 0)
    cheap, dear = _signed(b, 0, gas_price=2), _signed(b, 0, gas_price=5, value=2)
    assert pool.add(included) and pool.add(cheap)
    # the block that held `included` committed; no barrier ran in between
    state[addr_a] = 1
    pool.remove_included([included.hash()])
    kv.calls.clear()
    assert pool.add(dear), "a strictly higher fee replaces"
    assert kv.calls == [("write_batch_async", [_row(dear)], [_row(cheap)])], (
        "the new row and the old row's delete ride in one atomic batch"
    )
    assert not pool.add(_signed(b, 0, gas_price=3, value=3))
    pool.barrier()
    kv.close()
    kv2 = LsmKV(path)
    try:
        pool2 = TransactionPool(kv2, CHAIN, lambda a: state.get(a, 0))
        assert pool2.persisted_hashes() == [dear.hash()]
        assert pool2.restore() == 1 and pool2.tx_hashes() == {dear.hash()}
    finally:
        kv2.close()


# -- (c) SIGKILL between the submits and any barrier -------------------------

_CHILD = """
import os, signal, sys
from lachain_tpu.core.tx_pool import TransactionPool
from lachain_tpu.core.types import SignedTransaction
from lachain_tpu.storage.lsm import LsmKV

kv = LsmKV(sys.argv[1])
pool = TransactionPool(kv, 225, lambda a: 0)
pool.frame_barrier()
for line in open(sys.argv[2]):
    assert pool.add(SignedTransaction.decode(bytes.fromhex(line.strip())))
assert pool.rows_pending()
sys.stdout.write("submitted\\n")
sys.stdout.flush()
os.kill(os.getpid(), signal.SIGKILL)
"""


@pytest.mark.parametrize("n", [1, 40, 400])
def test_sigkill_before_any_barrier_leaves_a_prefix_and_a_clean_store(tmp_path, n):
    """N rows submitted, no barrier, SIGKILL. Nothing acknowledged any of
    them, so any may be lost; the WAL is append-ordered, so what survives
    is the first k of them in submit order for some k, never a row without
    every row before it; and `fsck` finds nothing to repair."""
    keys = _keys(8, 300)
    txs = [_signed(keys[i % 8], i // 8) for i in range(n)]
    feed = tmp_path / "txs.hex"
    feed.write_text("".join(stx.encode().hex() + "\n" for stx in txs))
    script = tmp_path / "child.py"
    script.write_text(textwrap.dedent(_CHILD))
    path = str(tmp_path / "pool")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    child = subprocess.run(
        [sys.executable, str(script), path, str(feed)],
        env=env, capture_output=True, timeout=120,
    )
    assert child.returncode == -signal.SIGKILL, child.stderr.decode()[-2000:]
    assert child.stdout.decode().strip() == "submitted"
    kv = LsmKV(path)
    try:
        report = fsck(kv, repair=False)
        assert report.clean, report.to_dict()
        pool = TransactionPool(kv, CHAIN, lambda a: 0)
        survived = set(pool.persisted_hashes())
        order = [stx.hash() for stx in txs]
        assert survived == set(order[: len(survived)]), (
            "a row survived without every row submitted before it"
        )
        assert pool.restore() == len(survived)
        assert pool.tx_hashes() == survived
    finally:
        kv.close()


# -- (d) nobody took the barrier, or no overlapping WAL: today's calls -------


def _open_spy(engine, tmp_path):
    if engine == "memory":
        return _spy(MemoryKV)()
    if engine == "sqlite":
        return _spy(SqliteKV)(str(tmp_path / "p.db"))
    return _spy(LsmKV)(str(tmp_path / "p"))


@pytest.mark.parametrize(
    "engine,barrier_taken",
    [
        ("lsm", False),
        ("sqlite", False),
        ("memory", False),
        ("sqlite", True),
        ("memory", True),
    ],
)
def test_without_a_frame_boundary_or_a_wal_add_issues_todays_calls(
    tmp_path, engine, barrier_taken
):
    """A standalone pool (the devnet, the crash workload) and a pool on a
    store whose `write_batch_async` is the synchronous write: one `put` an
    admission, one `write_batch` a replacement, durable on return, no
    ticket, no counter — what the crash matrix's traversal counts are
    written against (tests/test_crashpoints.py)."""
    kv = _open_spy(engine, tmp_path)
    pool = TransactionPool(kv, CHAIN, lambda a: 0)
    hook = pool.frame_barrier() if barrier_taken else pool.barrier
    (a,) = _keys(1, 400)
    cheap, dear = _signed(a, 0, gas_price=2), _signed(a, 0, gas_price=5, value=2)
    before = _counters()
    assert pool.add(cheap)
    assert kv.calls == [("put", _row(cheap))]
    assert not pool.rows_pending()
    assert kv.get(_row(cheap)) == cheap.encode()
    kv.calls.clear()
    assert pool.add(dear)
    assert kv.calls == [("write_batch", [_row(dear)], [_row(cheap)])]
    kv.calls.clear()
    hook()
    assert kv.calls == [] and not pool.rows_pending()
    assert _counters() == before
    assert pool.persisted_hashes() == [dear.hash()]
    if engine != "memory":
        kv.close()


# -- (e) a served node: no write to a socket outruns an admitted row ---------


@pytest.mark.parametrize("hook", ["kept", "removed"])
def test_no_frame_leaves_while_a_pool_row_is_pending(hook):
    """N=4 nodes over TCP, each on a KV whose async batches are durable only
    after a barrier; a client submits to every node while eras run. At the
    moment of each write to a socket the writing node's pool holds no
    pending ticket and no undurable row. With the hook removed the same
    check fails: it can fail."""
    from lachain_tpu.core.node import Node

    pub, privs = trusted_key_gen(4, 1, rng=Rng(41))
    clients = _keys(8, 500)
    addrs = [
        ecdsa.address_from_public_key(ecdsa.public_key_bytes(k)) for k in clients
    ]
    violations = []
    gossiped = [0]

    def watch(node):
        send = node.network.hub.send_raw

        async def checking_send(dest, data):
            if node.pool.rows_pending() or node.kv.undurable_pool_rows():
                violations.append(node.index)
            batch = wire.MessageBatch.decode(data)
            gossiped[0] += sum(
                1 for m in batch.messages() if m.kind == wire.KIND_SYNC_POOL_REPLY
            )
            return await send(dest, data)

        node.network.hub.send_raw = checking_send

    async def run():
        nodes = [
            Node(
                index=i, public_keys=pub, private_keys=privs[i], chain_id=CHAIN,
                kv=WalKV(), initial_balances={a: 10**21 for a in addrs},
                flush_interval=0.01, txs_per_block=100,
            )
            for i in range(4)
        ]
        for nd in nodes:
            if hook == "removed":
                nd.network._barrier = None
            watch(nd)
            await nd.start()
        net = [nd.network.address for nd in nodes]
        for i, nd in enumerate(nodes):
            nd.connect([a for j, a in enumerate(net) if j != i])

        async def client():
            for nonce in range(6):
                for j, key in enumerate(clients):
                    assert nodes[j % 4].submit_tx(_signed(key, nonce))
                    await asyncio.sleep(0.002)

        try:
            feeding = asyncio.ensure_future(client())
            for era in (1, 2):
                await asyncio.gather(*(nd.run_era(era, timeout=60.0) for nd in nodes))
            await feeding
            await asyncio.sleep(0.05)  # the last admissions' frames
        finally:
            for nd in nodes:
                await nd.stop()
        return nodes

    waits = metrics.counter_value(WAITS)
    rows = metrics.counter_value(ROWS)
    nodes = asyncio.run(run())
    waits = metrics.counter_value(WAITS) - waits
    rows = metrics.counter_value(ROWS) - rows
    assert gossiped[0] >= 48, "the frames carried too few admitted transactions"
    assert rows >= 4 * 48, "every node admits every transaction: own and gossiped"
    if hook == "kept":
        assert violations == []
        assert 0 < waits < rows, "one wait a frame, and a frame follows many rows"
    else:
        assert violations, "without the hook a frame must outrun an admitted row"


def test_a_barrier_that_raises_holds_the_frame_back_and_keeps_the_ticket():
    kv = WalKV()
    pool = TransactionPool(kv, CHAIN, lambda a: 0)
    (a,) = _keys(1, 600)
    stx = _signed(a, 0)
    frames = []

    async def run():
        class Hub:
            async def send_raw(self, peer, data):
                frames.append(data)
                return True

        (priv,) = _keys(1, 601)
        worker = ClientWorker(
            None, wire.MessageFactory(priv), Hub(), flush_interval=0.002,
            barrier=pool.frame_barrier(),
        )
        kv.fail_barriers = 2
        assert pool.add(stx)
        worker.enqueue(wire.sync_pool_reply([stx]))
        worker.start()
        for _ in range(400):
            await asyncio.sleep(0.005)
            if len(kv.barrier_calls) >= 2 and not frames:
                # two waits failed: nothing left, and the ticket is kept
                assert pool.rows_pending()
                assert kv.undurable_pool_rows() == {_row(stx)}
            if frames:
                break
        await worker.stop()

    failures = metrics.counter_value("network_barrier_failures_total")
    asyncio.run(run())
    assert metrics.counter_value("network_barrier_failures_total") - failures == 2
    assert len(kv.barrier_calls) == 3 and len(set(kv.barrier_calls)) == 1, (
        "the same ticket is waited for until the WAL takes it"
    )
    assert len(frames) == 1 and not pool.rows_pending()
    assert kv.undurable_pool_rows() == set()


# -- (f) the RPC answer ------------------------------------------------------


class _BlockingWalKV(WalKV):
    """`write_barrier` returns only once the event loop has run another
    task several times: a wait taken ON the loop would never see that."""

    def __init__(self):
        super().__init__()
        self.ticks = [0]
        self.ticks_seen_inside = None
        self.returned_at = None
        self.thread = None

    def write_barrier(self, ticket):
        self.thread = threading.current_thread()
        start = self.ticks[0]
        deadline = time.monotonic() + 10.0
        while self.ticks[0] < start + 5 and time.monotonic() < deadline:
            time.sleep(0.002)
        self.ticks_seen_inside = self.ticks[0] - start
        super().write_barrier(ticket)
        self.returned_at = time.monotonic()


class _ServedPool:
    """What RpcService needs of a node to take a submission."""

    def __init__(self, kv):
        self.chain_id = CHAIN
        self.pool = TransactionPool(kv, CHAIN, lambda a: 0)
        self.pool.frame_barrier()

    def submit_tx(self, stx):
        return self.pool.add(stx)


async def _post(port, method, *params):
    body = json.dumps(
        {"jsonrpc": "2.0", "id": 1, "method": method, "params": list(params)}
    ).encode()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(
        b"POST / HTTP/1.1\r\nHost: x\r\nContent-Type: application/json\r\n"
        + f"Content-Length: {len(body)}\r\n\r\n".encode()
        + body
    )
    await writer.drain()
    head = await reader.readuntil(b"\r\n\r\n")
    length = int(
        [h for h in head.decode().split("\r\n") if h.lower().startswith("content-length")][0]
        .split(":")[1]
    )
    answer = json.loads(await reader.readexactly(length))
    writer.close()
    return answer


@pytest.mark.parametrize(
    "method", ["eth_sendRawTransaction", "la_sendRawTransactionBatch"]
)
def test_the_rpc_answer_waits_for_the_barrier_off_the_loop(method):
    from lachain_tpu.rpc import JsonRpcServer, RpcService

    kv = _BlockingWalKV()
    node = _ServedPool(kv)
    (a,) = _keys(1, 700)
    txs = [_signed(a, nonce) for nonce in range(3)]
    raws = ["0x" + stx.encode().hex() for stx in txs]

    async def run():
        server = JsonRpcServer("127.0.0.1", 0)
        server.register_all(RpcService(node).methods())
        await server.start()

        async def ticker():
            while True:
                kv.ticks[0] += 1
                await asyncio.sleep(0.002)

        ticking = asyncio.ensure_future(ticker())
        try:
            if method == "eth_sendRawTransaction":
                answer = await _post(server.port, method, raws[0])
            else:
                answer = await _post(server.port, method, raws)
            answered_at = time.monotonic()
        finally:
            ticking.cancel()
            await server.stop()
        return answer, answered_at

    answer, answered_at = asyncio.run(run())
    if method == "eth_sendRawTransaction":
        assert answer["result"] == "0x" + txs[0].hash().hex()
        assert kv.barrier_calls == [1]
    else:
        assert answer["result"] == ["0x" + stx.hash().hex() for stx in txs]
        assert kv.barrier_calls == [3], "one wait for the batch, the newest ticket"
    assert kv.returned_at is not None and kv.returned_at <= answered_at
    assert kv.thread is not threading.main_thread(), "the wait left the loop's thread"
    assert kv.ticks_seen_inside >= 5, "the loop ran another task meanwhile"
    assert not node.pool.rows_pending() and kv.undurable_pool_rows() == set()


def test_an_rpc_submission_with_nothing_pending_answers_in_place():
    """A store without an overlapping WAL (or a caller outside any loop):
    the handler returns the answer itself, as it always did, so a service
    driven synchronously keeps working."""
    from lachain_tpu.rpc import RpcService

    (a,) = _keys(1, 800)
    memory = _ServedPool(MemoryKV())
    raw = "0x" + _signed(a, 0).encode().hex()
    assert RpcService(memory).eth_sendRawTransaction(raw) == "0x" + _signed(a, 0).hash().hex()
    wal = WalKV()
    served = _ServedPool(wal)
    # no running loop: the wait is taken in place, before the answer
    assert RpcService(served).eth_sendRawTransaction(raw).startswith("0x")
    assert wal.barrier_calls == [1] and not served.pool.rows_pending()
