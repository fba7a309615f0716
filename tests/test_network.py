"""Networking tests: wire codecs, signed batches, hub transport, priorities,
and the full 4-validator consensus over localhost TCP.

Mirrors the reference's networking layer behavior (SURVEY.md §2f:
NetworkManagerBase dispatch + signature verification, ClientWorker
batching/priorities, MessageFactory signed envelopes) — plus the end-to-end
flow the reference only exercises in a manual docker-compose devnet."""
import asyncio
import random

import pytest

from lachain_tpu.consensus import messages as M
from lachain_tpu.consensus.keys import trusted_key_gen
from lachain_tpu.core import execution
from lachain_tpu.core.node import Node
from lachain_tpu.core.types import Transaction, sign_transaction
from lachain_tpu.crypto import ecdsa
from lachain_tpu.network import wire
from lachain_tpu.network.manager import NetworkManager

CHAIN = 225


class Rng:
    def __init__(self, seed=1):
        self._r = random.Random(seed)

    def randbelow(self, n):
        return self._r.randrange(n)


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

PAYLOADS = [
    M.ValMessage(
        rbc=M.ReliableBroadcastId(era=3, sender_id=1),
        root=b"\x11" * 32,
        branch=(b"\x22" * 32, b"\x33" * 32),
        shard=b"shard-data",
        shard_index=2,
    ),
    M.EchoMessage(
        rbc=M.ReliableBroadcastId(era=3, sender_id=0),
        root=b"\x44" * 32,
        branch=(),
        shard=b"",
        shard_index=0,
    ),
    M.ReadyMessage(rbc=M.ReliableBroadcastId(era=3, sender_id=2), root=b"\x55" * 32),
    M.BValMessage(bb=M.BinaryBroadcastId(era=3, agreement=1, epoch=0), value=True),
    M.AuxMessage(bb=M.BinaryBroadcastId(era=3, agreement=-1, epoch=2), value=False),
    M.ConfMessage(
        bb=M.BinaryBroadcastId(era=3, agreement=0, epoch=4),
        values=frozenset({True, False}),
    ),
    M.CoinMessage(coin=M.CoinId(era=3, agreement=-1, epoch=0), share=b"\x66" * 96),
    M.DecryptedMessage(hb=M.HoneyBadgerId(era=3), share_id=1, payload=b"\x77" * 48),
    M.SignedHeaderMessage(
        root=M.RootProtocolId(era=3), header_bytes=b"\x88" * 88, signature=b"\x99" * 65
    ),
]


def test_payload_codec_roundtrip():
    for p in PAYLOADS:
        assert wire.decode_payload(wire.encode_payload(p)) == p


def test_consensus_msg_roundtrip():
    for p in PAYLOADS:
        era, back = wire.parse_consensus(wire.consensus_msg(3, p))
        assert era == 3 and back == p


def test_batch_sign_verify_and_tamper():
    factory = wire.MessageFactory(ecdsa.generate_private_key(Rng()))
    batch = factory.batch([wire.ping_request(7), wire.ping_reply(9)])
    encoded = batch.encode()
    back = wire.MessageBatch.decode(encoded)
    assert back.verify()
    msgs = back.messages()
    assert [m.kind for m in msgs] == [wire.KIND_PING_REQUEST, wire.KIND_PING_REPLY]
    assert wire.parse_height(msgs[0]) == 7
    # tamper with the content -> signature check fails
    bad = wire.MessageBatch(back.sender, back.signature, back.content + b"x")
    assert not bad.verify()


def test_sync_codecs_roundtrip():
    priv = ecdsa.generate_private_key(Rng(3))
    tx = Transaction(to=b"\x0a" * 20, value=5, nonce=0, gas_price=1, gas_limit=21000)
    stx = sign_transaction(tx, priv, CHAIN)
    msg = wire.sync_pool_reply([stx])
    assert wire.parse_sync_pool_reply(msg) == [stx]
    req = wire.sync_blocks_request(10, 5)
    assert wire.parse_sync_blocks_request(req) == (10, 5)
    preq = wire.sync_pool_request([stx.hash()])
    assert wire.parse_sync_pool_request(preq) == [stx.hash()]


# ---------------------------------------------------------------------------
# transport
# ---------------------------------------------------------------------------


def test_manager_ping_roundtrip():
    async def main():
        a = NetworkManager(ecdsa.generate_private_key(Rng(1)), flush_interval=0.01)
        b = NetworkManager(ecdsa.generate_private_key(Rng(2)), flush_interval=0.01)
        got = asyncio.Event()
        seen = {}

        def on_req(sender, height):
            seen["req"] = (sender, height)
            b.send_to(sender, wire.ping_reply(42))

        def on_reply(sender, height):
            seen["reply"] = (sender, height)
            got.set()

        b.on_ping_request = on_req
        a.on_ping_reply = on_reply
        await a.start()
        await b.start()
        a.add_peer(b.address)
        b.add_peer(a.address)
        a.send_to(b.public_key, wire.ping_request(7))
        await asyncio.wait_for(got.wait(), 5)
        assert seen["req"] == (a.public_key, 7)
        assert seen["reply"] == (b.public_key, 42)
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_hub_stop_returns_with_a_peer_connected_whose_reader_started_late():
    """A connection accepted as stop() closes the server may start its
    reader only after stop() cancelled the others; stop() must not then
    wait for that peer to hang up (PR 40)."""
    from lachain_tpu.network.hub import Hub

    async def main():
        hub = Hub("127.0.0.1", 0, on_batch=lambda data: None)
        reader_of, accepted, late = hub._handle_inbound, asyncio.Event(), asyncio.Event()

        async def handle(reader, writer):  # its first step, held past stop()'s cancel
            accepted.set()
            await late.wait()
            await reader_of(reader, writer)

        hub._handle_inbound = handle
        await hub.start()
        _peer_reader, peer = await asyncio.open_connection("127.0.0.1", hub.port)
        try:
            await asyncio.wait_for(accepted.wait(), 5)
            stopping = asyncio.ensure_future(hub.stop())
            await asyncio.sleep(0.05)
            late.set()
            await asyncio.wait_for(stopping, 5)
        finally:
            peer.close()

    asyncio.run(main())


def test_forged_batch_dropped():
    async def main():
        a = NetworkManager(ecdsa.generate_private_key(Rng(1)), flush_interval=0.01)
        b = NetworkManager(ecdsa.generate_private_key(Rng(2)), flush_interval=0.01)
        hits = []
        b.on_ping_request = lambda s, h: hits.append((s, h))
        await a.start()
        await b.start()
        # craft a batch whose signature does not match the claimed sender
        good = a.factory.batch([wire.ping_request(1)])
        forged = wire.MessageBatch(
            sender=b.public_key, signature=good.signature, content=good.content
        )
        await a.hub.send_raw(b.address, forged.encode())
        # then a valid one so we know delivery happened
        await a.hub.send_raw(b.address, good.encode())
        for _ in range(100):
            if hits:
                break
            await asyncio.sleep(0.01)
        assert hits == [(a.public_key, 1)]
        await a.stop()
        await b.stop()

    asyncio.run(main())


def test_worker_priority_ordering():
    """Replies flush before consensus before pool-sync requests
    (reference NetworkMessagePriority)."""
    from lachain_tpu.network.worker import ClientWorker

    async def main():
        sent = []

        class FakeHub:
            async def send_raw(self, peer, data):
                batch = wire.MessageBatch.decode(data)
                sent.extend(batch.messages())
                return True

        factory = wire.MessageFactory(ecdsa.generate_private_key(Rng()))
        w = ClientWorker(None, factory, FakeHub(), flush_interval=0.05)
        w.enqueue(wire.sync_pool_request([b"\x01" * 32]))
        w.enqueue(wire.consensus_msg(1, PAYLOADS[3]))
        w.enqueue(wire.ping_reply(5))
        w.start()
        await asyncio.sleep(0.2)
        await w.stop()
        kinds = [m.kind for m in sent]
        assert kinds == [
            wire.KIND_PING_REPLY,
            wire.KIND_CONSENSUS,
            wire.KIND_SYNC_POOL_REQUEST,
        ]

    asyncio.run(main())


# ---------------------------------------------------------------------------
# 4-validator consensus over real TCP
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_four_node_consensus_over_tcp():
    n, f = 4, 1
    pub, privs = trusted_key_gen(n, f, rng=Rng(99))
    user_priv = ecdsa.generate_private_key(Rng(5))
    user_addr = ecdsa.address_from_public_key(ecdsa.public_key_bytes(user_priv))
    dest = b"\x0d" * 20
    genesis = {user_addr: 10**21}

    async def main():
        nodes = [
            Node(
                index=i,
                public_keys=pub,
                private_keys=privs[i],
                chain_id=CHAIN,
                initial_balances=genesis,
                txs_per_block=100,
                flush_interval=0.01,
            )
            for i in range(n)
        ]
        for node in nodes:
            await node.start()
        addrs = [node.address for node in nodes]
        for node in nodes:
            node.connect(addrs)

        # a user tx lands on node 0 and gossips to the others
        tx = Transaction(
            to=dest, value=777, nonce=0, gas_price=1, gas_limit=21000
        )
        stx = sign_transaction(tx, user_priv, CHAIN)
        assert nodes[0].submit_tx(stx)
        for _ in range(200):
            if all(len(node.pool) == 1 for node in nodes):
                break
            await asyncio.sleep(0.01)
        assert all(len(node.pool) == 1 for node in nodes), "tx gossip failed"

        blocks1 = await asyncio.gather(*(node.run_era(1) for node in nodes))
        assert len({b.hash() for b in blocks1}) == 1, "fork at era 1"
        blocks2 = await asyncio.gather(*(node.run_era(2) for node in nodes))
        assert len({b.hash() for b in blocks2}) == 1, "fork at era 2"

        for node in nodes:
            assert node.block_manager.current_height() == 2
            snap = node.state.new_snapshot()
            assert execution.get_balance(snap, dest) == 777
        assert stx.hash() in {h for b in blocks1 + blocks2 for h in b.tx_hashes}

        for node in nodes:
            await node.stop()

    asyncio.run(main())


def test_zip_bomb_batch_rejected():
    # a small compressed frame that expands past the 64 MiB cap must be
    # rejected without ever materializing the full decompressed output
    import zlib

    from lachain_tpu.crypto import ecdsa as _ecdsa
    from lachain_tpu.crypto.hashes import keccak256
    from lachain_tpu.network.wire import MessageBatch

    priv = _ecdsa.generate_private_key()
    bomb = zlib.compress(b"\x00" * (1 << 28), level=9)  # 256 MiB -> ~256 KiB
    assert len(bomb) < 1 << 20
    batch = MessageBatch(
        sender=_ecdsa.public_key_bytes(priv),
        signature=_ecdsa.sign_hash(priv, keccak256(bomb)),
        content=bomb,
    )
    assert batch.verify()
    with pytest.raises(ValueError):
        batch.messages()


@pytest.mark.slow
def test_consensus_survives_severed_connections():
    """VERDICT r2 #6 acceptance: sever every TCP connection of one
    validator mid-era; consensus must complete after the transport
    reconnects (reference hub redial behavior, Hub/HubConnector.cs:26-105).
    Send-side sockets redial on demand with exponential backoff; the
    severed node's inbound server keeps accepting."""
    import asyncio
    import random as _random

    from lachain_tpu.consensus.keys import trusted_key_gen
    from lachain_tpu.core.node import Node

    class Rng:
        def __init__(self, seed):
            self._r = _random.Random(seed)

        def randbelow(self, n):
            return self._r.randrange(n)

    async def run():
        n, f = 4, 1
        pub, privs = trusted_key_gen(n, f, rng=Rng(21))
        nodes = [
            Node(
                index=i,
                public_keys=pub,
                private_keys=privs[i],
                chain_id=515,
                flush_interval=0.01,
            )
            for i in range(n)
        ]
        for node in nodes:
            await node.start()
        addrs = [node.address for node in nodes]
        for node in nodes:
            node.connect(addrs)
        tasks = [
            asyncio.ensure_future(node.run(first_era=1, stop_at=4))
            for node in nodes
        ]
        # let era 1 get going, then sever node 0's sockets in both
        # directions (outbound cached writers + everyone's writer TO it)
        await asyncio.sleep(0.4)
        victim = nodes[0]
        for w in list(victim.network.hub._conns.values()):
            w.close()
        victim.network.hub._conns.clear()
        for other in nodes[1:]:
            for w in list(other.network.hub._conns.values()):
                w.close()
            other.network.hub._conns.clear()
        done, pending = await asyncio.wait(tasks, timeout=120)
        for t in pending:
            t.cancel()
        assert not pending, "consensus did not recover after sever"
        for t in done:
            t.result()
        heights = [nd.block_manager.current_height() for nd in nodes]
        assert all(h >= 4 for h in heights), heights
        for node in nodes:
            await node.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# fault injection + retransmission/recovery
# ---------------------------------------------------------------------------


def test_message_request_codec_roundtrip():
    msg = wire.message_request(17)
    assert msg.kind == wire.KIND_MESSAGE_REQUEST
    assert wire.parse_message_request(msg) == 17


def test_worker_backoff_jitter_and_reset():
    """A failing transport backs off exponentially with seeded ±25% jitter
    (a fleet redialing in lockstep would re-stampede a returning peer) and
    counts its attempts; reset_backoff() arms an immediate retry."""
    from lachain_tpu.network.worker import ClientWorker
    from lachain_tpu.utils import metrics

    async def main():
        attempts = []

        class DeadHub:
            async def send_raw(self, peer, data):
                attempts.append(1)
                return False

        before = metrics.counter_value("network_reconnect_attempts_total")
        factory = wire.MessageFactory(ecdsa.generate_private_key(Rng()))
        w = ClientWorker(None, factory, DeadHub(), flush_interval=0.01)
        w.enqueue(wire.ping_reply(5))
        w.start()
        for _ in range(200):
            if len(attempts) >= 2:
                break
            await asyncio.sleep(0.01)
        assert len(attempts) >= 2
        assert w.consecutive_failures >= 2
        assert w._backoff > w._flush_interval  # grew exponentially
        after = metrics.counter_value("network_reconnect_attempts_total")
        assert after - before >= 2
        w.reset_backoff()
        assert w._backoff == w._flush_interval
        w._stopped = True  # skip final-flush hang against the dead hub
        w._wakeup.set()
        # jitter factor stays inside ±25% of the nominal backoff
        for _ in range(64):
            assert 0.75 <= 0.75 + 0.5 * w._jitter.random() <= 1.25

    asyncio.run(main())


def test_undelivered_cap_drop_is_observable():
    """Overflowing the unknown-peer buffer must log + count the loss
    (a silently-vanished consensus message is the wedged-era failure
    mode), not discard silently."""
    from lachain_tpu.utils import metrics

    m = NetworkManager(ecdsa.generate_private_key(Rng(7)))
    m._undelivered_cap = 4
    ghost = b"\x03" * 33  # never-connected peer
    before = metrics.counter_value(
        "network_undelivered_dropped_total", labels={"kind": str(wire.KIND_PING_REQUEST)}
    )
    for _ in range(6):
        m.send_to(ghost, wire.ping_request(1))
    assert len(m._undelivered[ghost]) == 4
    after = metrics.counter_value(
        "network_undelivered_dropped_total", labels={"kind": str(wire.KIND_PING_REQUEST)}
    )
    assert after - before == 2


@pytest.mark.slow
@pytest.mark.chaos
def test_tcp_outbox_replay_heals_frame_loss():
    """End-to-end recovery ladder over real sockets: TcpFrameFilters on
    nodes 0 AND 1 block their outbound frames for a wall-clock window
    (frames are dropped while REPORTING SUCCESS, so the worker's requeue
    path cannot mask the loss — exactly like real network loss). With two
    of four senders mute, no 2f+1=3 quorum exists and the era MUST wedge;
    every message the mute pair sent into the window is gone and consensus
    never retransmits. Watchdogs escalate to message_request broadcasts,
    and once the window heals the lost traffic comes back exclusively via
    per-era outbox replay. The era must complete on every node."""
    from lachain_tpu.network.faults import FaultPlan, Partition
    from lachain_tpu.utils import metrics

    n, f = 4, 1
    pub, privs = trusted_key_gen(n, f, rng=Rng(31))

    async def run():
        nodes = [
            Node(
                index=i,
                public_keys=pub,
                private_keys=privs[i],
                chain_id=616,
                flush_interval=0.01,
            )
            for i in range(n)
        ]
        for node in nodes:
            # tight recovery ladder so the test runs in seconds: sweep at
            # 4 Hz, strike after 0.5s quiet, serve replays at 10 Hz
            node.watchdog_interval = 0.25
            node.stall_timeout = 0.5
            node.replay_min_interval = 0.1
            await node.start()
        addrs = [node.address for node in nodes]
        for node in nodes:
            node.connect(addrs)
        # nodes 0 and 1 cannot send to ANYONE (each other included) for
        # 1.5 wall seconds; inbound still flows (only senders filter)
        plan = FaultPlan(
            seed=5,
            partitions=(
                Partition(frozenset({0, 1}), frozenset({2, 3}), at=0.0, heal=1.5),
                Partition(frozenset({0}), frozenset({1}), at=0.0, heal=1.5),
            ),
        )
        filters = []
        for victim in (0, 1):
            filt = nodes[victim].network.install_faults(plan, my_id=victim)
            for i, node in enumerate(nodes):
                nodes[victim].network.map_fault_peer(
                    node.network.public_key, i
                )
            filters.append(filt)

        replayed_before = metrics.counter_value(
            "consensus_outbox_replayed_total"
        )
        blocks = await asyncio.wait_for(
            asyncio.gather(*(node.run_era(1) for node in nodes)), 90
        )
        assert len({b.hash() for b in blocks}) == 1, "fork after recovery"
        assert all(
            node.block_manager.current_height() == 1 for node in nodes
        )
        # the fault actually fired, and recovery came from outbox replay
        assert all(f.session.stats["blocked"] > 0 for f in filters)
        replayed_after = metrics.counter_value(
            "consensus_outbox_replayed_total"
        )
        assert replayed_after - replayed_before > 0
        for node in nodes:
            await node.stop()

    asyncio.run(run())
