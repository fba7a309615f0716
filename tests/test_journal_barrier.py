"""Persist-before-transmit with the wait at the frame (consensus/journal.py).

The journal submits a record to the KV's WAL when the payload is handed to
the transport, and waits for its fsync where a frame leaves the node
(network/worker.py `_transmit`, network/manager.py `_send_inbound`). These
tests hold the guarantee under that timing: a frame never carries a payload whose record is not durable; a crash
between the record and the barrier leaves a record absent-and-unsent or
present-and-re-armed; where there is no frame boundary, or no overlapping
WAL, `record` is durable on return as it always was.
"""
import asyncio
import random

import pytest

from lachain_tpu.consensus import messages as M
from lachain_tpu.consensus.era import EraRouter
from lachain_tpu.consensus.journal import ConsensusJournal, send_slot
from lachain_tpu.consensus.keys import trusted_key_gen
from lachain_tpu.consensus.simulator import DeliveryMode, SimulatedNetwork
from lachain_tpu.crypto import ecdsa
from lachain_tpu.network import wire
from lachain_tpu.network.worker import ClientWorker
from lachain_tpu.storage import crashpoints
from lachain_tpu.storage.crashpoints import CrashPlan, CrashPoint, InjectedCrash
from lachain_tpu.storage.kv import EntryPrefix, MemoryKV, SqliteKV, prefixed
from lachain_tpu.storage.lsm import LsmKV
from lachain_tpu.utils import metrics
from lachain_tpu.utils.serialization import Reader

pytestmark = pytest.mark.crash

_JOURNAL = prefixed(EntryPrefix.CONSENSUS_STATE)


class Rng:
    def __init__(self, seed=1):
        self._r = random.Random(seed)

    def randbelow(self, n):
        return self._r.randrange(n)


class WalKV(MemoryKV):
    """A KV with an overlapping WAL, as a double: an async batch is visible
    at once (the memtable) and durable only after a barrier for its ticket,
    or after any later synchronous batch (an append-ordered WAL)."""

    supports_async_batches = True

    def __init__(self):
        super().__init__()
        self._seq = 0
        self._undurable = {}  # ticket -> keys put by that batch
        self.barrier_calls = []
        self.async_calls = 0

    def write_batch(self, puts, deletes=()):
        super().write_batch(puts, deletes)
        self._undurable.clear()

    def write_batch_async(self, puts, deletes=()):
        super().write_batch(puts, deletes)
        self.async_calls += 1
        self._seq += 1
        self._undurable[self._seq] = [k for k, _v in puts]
        return self._seq

    def write_barrier(self, ticket):
        self.barrier_calls.append(ticket)
        if ticket:
            for t in [t for t in self._undurable if t <= ticket]:
                del self._undurable[t]

    def durable_journal_payloads(self):
        """Wire bytes of every journal record a crash now would keep."""
        lost = {k for keys in self._undurable.values() for k in keys}
        out = set()
        for key, value in self.scan_prefix(_JOURNAL):
            if key not in lost:
                r = Reader(value)
                r.i64()
                out.add(r.bytes_())
        return out

    def after_power_loss(self):
        """The store a restart would open: what was durable, nothing else."""
        lost = {k for keys in self._undurable.values() for k in keys}
        kv = MemoryKV()
        kv.write_batch([(k, v) for k, v in self._d.items() if k not in lost])
        return kv


def _consensus_payloads(frame: bytes):
    for msg in wire.MessageBatch.decode(frame).messages():
        if msg.kind == wire.KIND_CONSENSUS:
            _era, payload = wire.parse_consensus(msg)
            yield wire.encode_payload(payload)


def _own_consensus_payloads(node, frame: bytes):
    """The consensus payloads of `node` itself in `frame`: its own batch,
    bare or inside the relay_forward envelopes it wraps for a NAT'd peer.
    A batch it only forwards for another node is that node's to journal."""
    batch = wire.MessageBatch.decode(frame)
    if batch.sender != node.network.public_key:
        return
    for msg in batch.messages():
        if msg.kind == wire.KIND_RELAY_FORWARD:
            _target, inner = wire.parse_relay_forward(msg)
            yield from _own_consensus_payloads(node, inner)
        elif msg.kind == wire.KIND_CONSENSUS:
            _era, payload = wire.parse_consensus(msg)
            yield wire.encode_payload(payload)


# -- (a) a fleet of real nodes: no frame outruns its records -----------------


@pytest.mark.parametrize(
    "topology,hook",
    [
        ("direct", "kept"),
        ("direct", "removed"),
        ("relay", "kept"),
        ("relay", "removed-at-relay"),
    ],
)
def test_no_frame_leaves_before_its_records_are_durable(topology, hook):
    """N=4 nodes over TCP, each on a KV whose async batches are durable only
    after a barrier; every write to a socket is checked at the moment it
    is made: a worker's frame (`hub.send_raw`) and a relay's reverse
    delivery to its client, which has no worker (`hub.send_on_conn`). In
    the relay topology validator 3 is NAT'd and registers with validator 0
    (tests/test_relay.py), so 0 answers it over its inbound connection.
    With the hook removed the same check fails: it can fail. At the relay
    it is removed from the reverse delivery alone, and that path fails."""
    from lachain_tpu.core.node import Node

    pub, privs = trusted_key_gen(4, 1, rng=Rng(31))
    addrs = [ecdsa.address_from_public_key(pk) for pk in pub.ecdsa_pub_keys]
    violations = []
    checked = {"send_raw": 0, "send_on_conn": 0}

    def watch(node, name):
        send = getattr(node.network.hub, name)

        async def checking_send(dest, data):
            durable = node.kv.durable_journal_payloads()
            for payload in _own_consensus_payloads(node, data):
                checked[name] += 1
                if payload not in durable:
                    violations.append((node.index, name))
            return await send(dest, data)

        setattr(node.network.hub, name, checking_send)

    async def run():
        nodes = [
            Node(
                index=i,
                public_keys=pub,
                private_keys=privs[i],
                chain_id=225,
                kv=WalKV(),
                initial_balances={a: 10**21 for a in addrs},
                flush_interval=0.01,
                txs_per_block=100,
            )
            for i in range(4)
        ]
        for nd in nodes:
            if hook == "removed":
                nd.network._barrier = None
            watch(nd, "send_raw")
            watch(nd, "send_on_conn")
            await nd.start()
        net = [nd.network.address for nd in nodes]
        if topology == "direct":
            for i, nd in enumerate(nodes):
                nd.connect([a for j, a in enumerate(net) if j != i])
        else:
            nodes[3].network.use_relay(net[0], reregister_every=5.0)
            for i in range(3):
                nodes[i].connect([a for j, a in enumerate(net[:3]) if j != i])
            nodes[3].connect(net[:3])
            pub3 = nodes[3].network.public_key
            for _ in range(80):
                await asyncio.sleep(0.05)
                if all(
                    nodes[i].network._relay_route.get(pub3) == net[0].public_key
                    for i in (1, 2)
                ):
                    break
            assert nodes[0].network.relay_clients
            if hook == "removed-at-relay":
                # the workers 0 has built keep theirs; what goes without is
                # the reverse delivery to 3, which no worker carries
                assert pub3 not in nodes[0].network._workers
                nodes[0].network._barrier = None
        try:
            for era in (1, 2, 3):
                blocks = await asyncio.gather(
                    *(nd.run_era(era, timeout=60.0) for nd in nodes)
                )
                assert len({b.header.state_hash for b in blocks}) == 1
        finally:
            for nd in nodes:
                await nd.stop()
        return nodes

    records = metrics.counter_value("consensus_journal_records_total")
    barriers = metrics.counter_value("consensus_journal_barriers_total")
    nodes = asyncio.run(run())
    records = metrics.counter_value("consensus_journal_records_total") - records
    barriers = metrics.counter_value("consensus_journal_barriers_total") - barriers
    assert checked["send_raw"] > 100, "the workers carried too few payloads"
    if topology == "relay":
        assert checked["send_on_conn"] >= 3, "the relay answered nothing of its own"
    assert all(nd.kv.async_calls > 0 for nd in nodes)
    if hook == "kept":
        assert violations == []
        # the wait is taken once a frame, and a frame carries several records
        assert 0 < barriers < records
    elif hook == "removed":
        assert violations, "without the hook a frame must outrun its record"
    else:
        assert (0, "send_on_conn") in violations, (
            "without its hook a reverse delivery must outrun its record"
        )


# -- (b) a crash between record and barrier ----------------------------------


def _run_one_era(n, pub, privs):
    """A whole era on the simulator; returns validator 0's inbox."""
    inbox = []

    class RecordingRouter(EraRouter):
        def dispatch_external(self, sender, payload):
            if self.my_id == 0:
                inbox.append((sender, payload))
            super().dispatch_external(sender, payload)

    net = SimulatedNetwork(
        pub, privs, seed=5, mode=DeliveryMode.TAKE_RANDOM,
        router_cls=RecordingRouter,
    )
    pid = M.HoneyBadgerId(era=0)
    for i in range(n):
        net.post_request(i, pid, b"tx-%d|" % i + bytes(16))
    assert net.run(lambda: all(r.result_of(pid) is not None for r in net.routers))
    return inbox


@pytest.mark.parametrize("engine", ["lsm", "wal-double"])
def test_crash_between_record_and_barrier(tmp_path, engine):
    """Validator 0 journals through the frame barrier and dies at
    `journal.barrier.pre`: records submitted, fsync not waited for, their
    frame not sent. After the restart every payload a frame carried is in
    the journal; every record that survived is re-armed and re-sent
    byte-identical under a different input and a shuffled inbox; a record
    that did not survive was carried by no frame. (LsmKV's WAL writer
    usually wins the race and keeps them all; the double loses every one.)"""
    n, f = 4, 1
    pub, privs = trusted_key_gen(n, f, rng=Rng(17))
    inbox = _run_one_era(n, pub, privs)
    pid = M.HoneyBadgerId(era=0)
    path = str(tmp_path / "j0")
    kv = LsmKV(path) if engine == "lsm" else WalKV()
    frames = []
    submitted = []

    async def crashed_run():
        class Hub:
            async def send_raw(self, peer, data):
                frames.append(data)
                return True

        journal = ConsensusJournal(kv)
        worker = ClientWorker(
            None,
            wire.MessageFactory(privs[0].ecdsa_priv),
            Hub(),
            flush_interval=0.002,
            barrier=journal.frame_barrier(),
        )

        def send(target, payload):
            submitted.append(wire.encode_payload(payload))
            worker.enqueue(wire.consensus_msg(0, payload))

        router = EraRouter(
            era=0, my_id=0, public_keys=pub, private_keys=privs[0],
            send=send, journal=journal,
        )
        worker.start()
        router.internal_request(
            M.Request(from_id=None, to_id=pid, input=b"tx-0|" + bytes(16))
        )
        for sender, payload in inbox:
            if worker._task.done():
                break  # the process is dead: nothing more happens in it
            router.dispatch_external(sender, payload)
            await asyncio.sleep(0.001)
        with pytest.raises(InjectedCrash):
            await worker._task

    with crashpoints.armed(
        CrashPlan(points=(CrashPoint(name="journal.barrier.pre", hit=4),))
    ) as session:
        asyncio.run(crashed_run())
    assert session.fired == [("journal.barrier.pre", 4)]
    assert len(frames) == 3, "three barriers passed, so three frames left"
    carried = [p for frame in frames for p in _consensus_payloads(frame)]
    assert 0 < len(carried) < len(submitted), "the window held no record"
    carried = set(carried)
    assert carried <= set(submitted)

    # restart over what the crash left
    if engine == "lsm":
        kv.close()
        kv2 = LsmKV(path)
    else:
        kv2 = kv.after_power_loss()
    try:
        journal2 = ConsensusJournal(kv2)
        recorded = {}
        survived = set()
        for era, _seq, _target, data in journal2.entries():
            survived.add(data)
            slot = send_slot(wire.decode_payload(data))
            if slot is not None:
                assert (era, slot) not in recorded, "slot journaled twice"
                recorded[(era, slot)] = data
        # the guarantee: what a frame carried is in the journal; and so a
        # record that is absent was carried by no frame
        assert carried <= survived
        if engine == "wal-double":
            assert set(submitted) - survived, "the double lost no record"
        assert recorded

        resent = []
        r2 = EraRouter(
            era=0, my_id=0, public_keys=pub, private_keys=privs[0],
            send=lambda t, p: resent.append(p), journal=journal2,
        )
        before = metrics.counter_value("consensus_journal_replayed_sends_total")
        for era, _seq, target, data in journal2.entries():  # _recover_journal
            r2.rearm_sent(era, target, data)
        assert r2.replay_outbox(0, 1) > 0
        r2.internal_request(
            M.Request(from_id=None, to_id=pid, input=b"DIFFERENT-BATCH")
        )
        shuffled = list(inbox)
        random.Random(99).shuffle(shuffled)
        for sender, payload in shuffled:
            r2.dispatch_external(sender, payload)
        checked = 0
        for payload in resent:
            slot = send_slot(payload)
            key = (r2._payload_era(payload), slot)
            if slot is not None and key in recorded:
                assert wire.encode_payload(payload) == recorded[key], (
                    f"self-equivocation on slot {key}"
                )
                checked += 1
        assert checked >= 1, "replay never exercised the latches"
        assert (
            metrics.counter_value("consensus_journal_replayed_sends_total")
            > before
        )
    finally:
        if engine == "lsm":
            kv2.close()


# -- (c) a barrier with nothing to wait for ----------------------------------


def test_barrier_without_a_ticket_makes_no_kv_call():
    kv = WalKV()
    journal = ConsensusJournal(kv)
    hook = journal.frame_barrier()
    before = metrics.counter_value("consensus_journal_barriers_total")
    hook()
    assert kv.barrier_calls == []
    journal.record(3, None, b"one")
    journal.record(3, 2, b"two")
    assert kv.barrier_calls == [], "record waits at the frame, not here"
    assert kv.durable_journal_payloads() == set()
    hook()
    assert kv.barrier_calls == [2], "one wait, for the newest ticket"
    assert kv.durable_journal_payloads() == {b"one", b"two"}
    hook()
    hook()
    assert kv.barrier_calls == [2]
    after = metrics.counter_value("consensus_journal_barriers_total")
    assert after - before == 1
    assert [(e, s, t, d) for e, s, t, d in journal.entries()] == [
        (3, 0, None, b"one"),
        (3, 1, 2, b"two"),
    ]


# -- (d) no frame boundary, or no overlapping WAL: durable on return ---------


def _open(engine, tmp_path):
    if engine == "memory":
        return MemoryKV()
    if engine == "sqlite":
        return SqliteKV(str(tmp_path / "j.db"))
    if engine == "lsm":
        return LsmKV(str(tmp_path / "j"))
    return WalKV()


@pytest.mark.parametrize("engine", ["memory", "sqlite", "lsm", "wal-double"])
@pytest.mark.parametrize("frames", [False, True], ids=["at-once", "frames"])
def test_record_is_durable_on_return_without_a_wal_or_a_frame(
    tmp_path, engine, frames
):
    """A journal nobody took the frame barrier of waits inside `record`, on
    every engine; with the barrier taken, an engine whose async batch is the
    synchronous default (ticket None) still has nothing left to wait for."""
    kv = _open(engine, tmp_path)
    waited = []
    inner = kv.write_barrier
    kv.write_barrier = lambda ticket: (waited.append(ticket), inner(ticket))[1]
    try:
        journal = ConsensusJournal(kv)
        if frames:
            journal.frame_barrier()
        journal.record(7, None, b"payload")
        overlapping = engine in ("lsm", "wal-double")
        if overlapping and frames:
            assert journal._ticket is not None and waited == []
            journal.barrier()
        assert journal._ticket is None, "nothing is left to wait for"
        assert len(waited) == (1 if overlapping else 0)
        if engine == "wal-double":
            assert kv.durable_journal_payloads() == {b"payload"}
        assert [d for _e, _s, _t, d in journal.entries()] == [b"payload"]
    finally:
        if engine in ("sqlite", "lsm"):
            kv.close()


def test_router_without_a_frame_boundary_sends_durable_records():
    """The simulator's routers deliver at once: each send finds its record
    durable already, on a KV where only a barrier makes it so."""
    n, f = 4, 1
    pub, privs = trusted_key_gen(n, f, rng=Rng(17))
    kvs = [WalKV() for _ in range(n)]
    journals = [ConsensusJournal(kv) for kv in kvs]
    sends = [0]

    class CheckingRouter(EraRouter):
        def __init__(self, **kw):
            send = kw.pop("send")

            def checked(target, payload):
                sends[0] += 1
                durable = kvs[kw["my_id"]].durable_journal_payloads()
                assert wire.encode_payload(payload) in durable
                send(target, payload)

            super().__init__(send=checked, journal=journals[kw["my_id"]], **kw)

    net = SimulatedNetwork(
        pub, privs, seed=5, mode=DeliveryMode.TAKE_RANDOM,
        router_cls=CheckingRouter,
    )
    pid = M.HoneyBadgerId(era=0)
    for i in range(n):
        net.post_request(i, pid, b"tx-%d|" % i + bytes(16))
    assert net.run(lambda: all(r.result_of(pid) is not None for r in net.routers))
    assert sends[0] > 40
    assert all(len(kv.barrier_calls) == kv.async_calls > 0 for kv in kvs)


# -- (e) the worker's flushes -------------------------------------------------


@pytest.mark.parametrize("path", ["tick", "final-flush-on-stop"])
def test_worker_barriers_before_every_transmit(path):
    events = []
    # the final flush is reached with a batch left over by a failed send
    answers = [True, True] if path == "tick" else [False, True]

    class Hub:
        async def send_raw(self, peer, data):
            events.append("send")
            return answers.pop(0)

    async def main():
        factory = wire.MessageFactory(ecdsa.generate_private_key(Rng()))
        w = ClientWorker(
            None, factory, Hub(), flush_interval=0.01,
            barrier=lambda: events.append("barrier"),
        )
        w.start()
        w.enqueue(wire.ping_reply(5))
        for _ in range(200):
            if events == ["barrier", "send"]:
                break
            await asyncio.sleep(0.005)
        assert events == ["barrier", "send"]
        if path == "tick":
            w.enqueue(wire.ping_reply(6))
            await asyncio.sleep(0.1)
        # else: the worker sleeps out its backoff with the batch requeued;
        # stop() ends the loop and the final flush takes the batch
        await w.stop()

    asyncio.run(main())
    assert events == ["barrier", "send"] * 2
    assert answers == []


def test_a_failed_barrier_keeps_its_ticket():
    """A WAL that cannot fsync fails the barrier, and keeps failing it: the
    ticket is not forgotten, so no later frame passes on an empty one."""

    class FailingWal(WalKV):
        def write_barrier(self, ticket):
            raise IOError("LSM write_barrier failed")

    journal = ConsensusJournal(FailingWal())
    hook = journal.frame_barrier()
    journal.record(1, None, b"p")
    for _ in range(2):
        with pytest.raises(IOError):
            hook()


def test_a_failed_barrier_is_a_failed_send_for_the_worker():
    """The worker holds the frame back, keeps its messages and its task,
    backs off, and sends the same messages once the barrier passes."""
    sent = []
    failures = [IOError("LSM write_barrier failed")] * 2

    class Hub:
        async def send_raw(self, peer, data):
            sent.append(data)
            return True

    def barrier():
        if failures:
            raise failures.pop()

    async def main():
        factory = wire.MessageFactory(ecdsa.generate_private_key(Rng()))
        w = ClientWorker(None, factory, Hub(), flush_interval=0.005, barrier=barrier)
        w.start()
        w.enqueue(wire.ping_reply(5))
        for _ in range(400):
            if sent:
                break
            assert not w._task.done(), "the worker died of a failed barrier"
            await asyncio.sleep(0.005)
        await w.stop()

    before = metrics.counter_value("network_barrier_failures_total")
    asyncio.run(main())
    assert failures == []
    assert metrics.counter_value("network_barrier_failures_total") - before == 2
    assert len(sent) == 1
    (msg,) = wire.MessageBatch.decode(sent[0]).messages()
    assert msg.body == wire.ping_reply(5).body


def test_a_failed_barrier_is_a_failed_send_for_a_reverse_delivery():
    """A relay's answer to its client waits in the undelivered buffer, as
    after a write that failed; nothing reaches the connection."""
    from lachain_tpu.network.manager import NetworkManager

    def barrier():
        raise IOError("LSM write_barrier failed")

    async def main():
        nm = NetworkManager(
            ecdsa.generate_private_key(Rng(2)), barrier=barrier
        )
        wrote = []

        async def send_on_conn(conn_id, data):
            wrote.append(data)
            return True

        nm.hub.send_on_conn = send_on_conn
        client = ecdsa.public_key_bytes(ecdsa.generate_private_key(Rng(3)))
        nm._on_relay_register(client)
        nm._last_conn[client] = 7
        msg = wire.ping_reply(9)
        nm.send_to(client, msg)
        await asyncio.sleep(0.01)
        assert wrote == []
        assert nm._undelivered[client] == [msg]

    asyncio.run(main())

