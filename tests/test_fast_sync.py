"""Fast state sync: a fresh node reaches the chain head by downloading the
trie, not replaying blocks (reference FastSynchronizerBatch.cs /
StateDownloader.cs). The multi-peer scheduler suite below drives the
RequestManager-style downloader: per-peer failover, request-id reply
attribution, bounded frontier, poisoning bans, and snapshot shipping."""
import asyncio
import random

import pytest

from lachain_tpu.consensus.keys import PrivateConsensusKeys, trusted_key_gen
from lachain_tpu.core import execution
from lachain_tpu.core.devnet import (
    clone_store,
    fabricate_chain_store,
    fixture_account,
)
from lachain_tpu.core.node import Node
from lachain_tpu.core.types import Transaction, sign_transaction
from lachain_tpu.crypto import ecdsa
from lachain_tpu.network import wire
from lachain_tpu.network.faults import FaultPlan, KillSwitch, TcpFrameFilter
from lachain_tpu.storage.kv import EntryPrefix, MemoryKV, prefixed
from lachain_tpu.utils import metrics

pytestmark = pytest.mark.sync

CHAIN = 733
FIXTURE_SEED = 7


class Rng:
    def __init__(self, seed=1):
        self._r = random.Random(seed)

    def randbelow(self, n):
        return self._r.randrange(n)


@pytest.mark.slow
def test_fresh_node_fast_syncs_state_then_follows():
    n, f = 4, 1
    pub, privs = trusted_key_gen(n, f, rng=Rng(21))
    user = ecdsa.generate_private_key(Rng(5))
    uaddr = ecdsa.address_from_public_key(ecdsa.public_key_bytes(user))
    dest = b"\x0c" * 20
    genesis = {uaddr: 10**20}

    async def main():
        validators = [
            Node(
                index=i, public_keys=pub, private_keys=privs[i],
                chain_id=CHAIN, initial_balances=genesis, flush_interval=0.01,
            )
            for i in range(n)
        ]
        for node in validators:
            await node.start()
        addrs = [node.address for node in validators]
        for node in validators:
            node.connect(addrs)

        # build an 8-block chain with real state changes
        for era in range(1, 9):
            stx = sign_transaction(
                Transaction(
                    to=dest, value=10, nonce=era - 1, gas_price=1,
                    gas_limit=21000,
                ),
                user, CHAIN,
            )
            validators[0].submit_tx(stx)
            await asyncio.sleep(0.05)
            await asyncio.gather(*(v.run_era(era) for v in validators))

        # fresh observer: genesis only
        observer = Node(
            index=-1, public_keys=pub,
            private_keys=PrivateConsensusKeys.observer(
                ecdsa.generate_private_key(Rng(99))
            ),
            chain_id=CHAIN, initial_balances=genesis, flush_interval=0.01,
        )
        # reference sequencing: fast sync runs BEFORE the block
        # synchronizer starts, so replay doesn't race the state download
        await observer.start(start_synchronizer=False)
        observer.connect(addrs)
        for v in validators:
            v.connect([observer.address])

        fs = observer.fast_sync
        peer_pub = pub.ecdsa_pub_keys[0]
        synced = await fs.sync(peer_pub, timeout=30)
        observer.start_services()
        assert synced == 8
        assert observer.block_manager.current_height() == 8
        # the downloaded STATE is complete and correct — without replay
        snap = observer.state.new_snapshot()
        assert execution.get_balance(snap, dest) == 80
        assert execution.get_nonce(snap, uaddr) == 8
        # blocks 1..7 were never downloaded (that's the point)
        assert observer.block_manager.block_by_height(3) is None
        assert observer.block_manager.block_by_height(8) is not None

        # and normal sync continues from the fast-synced head
        await asyncio.gather(*(v.run_era(9) for v in validators))
        await observer.synchronizer.wait_for_height(9, timeout=30)
        assert (
            observer.block_manager.block_by_height(9).hash()
            == validators[0].block_manager.block_by_height(9).hash()
        )

        # a tampered reply is rejected: wrong roots for the header
        for node in validators + [observer]:
            await node.stop()

    asyncio.run(main())


def test_fast_sync_rejects_mismatched_roots():
    """Roots that do not hash to the block header's state_hash are refused
    (the trust anchor of the download)."""
    from lachain_tpu.storage.state import StateRoots

    n, f = 4, 1
    pub, privs = trusted_key_gen(n, f, rng=Rng(3))

    async def main():
        node = Node(
            index=0, public_keys=pub, private_keys=privs[0],
            chain_id=CHAIN, initial_balances={}, flush_interval=0.01,
        )
        await node.start()
        fs = node.fast_sync
        block = node.block_manager.block_by_height(0)
        bogus = StateRoots(balances=b"\x11" * 32)

        def fake_send(pub, msg):
            # peer answers with roots that do not match the header
            fs._reply = (block, bogus.encode())
            fs._reply_event.set()

        node.network.send_to = fake_send
        with pytest.raises(ValueError, match="roots do not match"):
            await fs.sync(b"\x02" + b"\x00" * 32, timeout=5)
        await node.stop()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# multi-peer scheduler suite: fabricated fixture chains (devnet helpers),
# serving validators over real TCP, observers downloading with failover


async def _cluster(pub, privs, *, accounts, n_servers, full=False):
    """Fabricate genesis + a signed block 1 with `accounts` synthetic
    balances, clone it into `n_servers` serving validators, start them."""
    template, block, roots = fabricate_chain_store(
        pub, privs, chain_id=CHAIN, accounts=accounts, seed=FIXTURE_SEED
    )
    servers = []
    for i in range(n_servers):
        node = Node(
            index=i, public_keys=pub, private_keys=privs[i],
            chain_id=CHAIN, kv=clone_store(template), flush_interval=0.01,
        )
        # serving throughput is not under test here (it gets its own test)
        node.fast_sync.serve_rate = 1e9
        node.fast_sync.serve_capacity = 1e9
        await node.start(start_synchronizer=full)
        servers.append(node)
    addrs = [s.address for s in servers]
    for s in servers:
        s.connect(addrs)
    return template, block, roots, servers


async def _observer(pub, seed=99):
    obs = Node(
        index=-1, public_keys=pub,
        private_keys=PrivateConsensusKeys.observer(
            ecdsa.generate_private_key(Rng(seed))
        ),
        chain_id=CHAIN, initial_balances={}, flush_interval=0.01,
    )
    await obs.start(start_synchronizer=False)
    return obs


def _join(obs, servers):
    obs.connect([s.address for s in servers])
    for s in servers:
        s.connect([obs.address])


def _kill(node) -> KillSwitch:
    """Simulated SIGKILL: the node goes dark in both directions but its
    kernel 'keeps the sockets open' (sends appear to succeed)."""
    ks = KillSwitch(node.network.hub.frame_filter)
    node.network.hub.frame_filter = ks
    ks.kill()
    return ks


async def _wait_counter(name, base, threshold, timeout=30.0):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while metrics.counter_value(name) - base < threshold:
        assert loop.time() < deadline, f"{name} never reached +{threshold}"
        await asyncio.sleep(0.005)


async def _stop_all(nodes):
    for node in nodes:
        await node.stop()


def _spot_check_balances(obs, accounts):
    snap = obs.state.new_snapshot()
    for i in (0, 1, accounts // 2, accounts - 1):
        addr = fixture_account(FIXTURE_SEED, i)
        assert execution.get_balance(snap, addr) == 10_000 + i


def test_multi_peer_sync_survives_kill_then_joins_consensus():
    """ISSUE acceptance slice: a fresh node fast-syncs a 100k+-node trie
    from 3 serving peers while one is killed mid-download (simulated drop —
    the slow variant SIGKILLs a real process), finishes from the survivors,
    passes fsck, then follows consensus-produced blocks."""
    n, f = 4, 1
    pub, privs = trusted_key_gen(n, f, rng=Rng(21))

    async def main():
        template, block, roots, validators = await _cluster(
            pub, privs, accounts=80_000, n_servers=4, full=True
        )
        # the fixture really is a 100k+-node trie
        st = validators[0].state
        total = sum(st.trie.node_count(r) for r in roots.all_roots())
        assert total >= 100_000

        obs = await _observer(pub)
        _join(obs, validators)
        fs = obs.fast_sync
        fs.request_timeout = 1.0
        serving = [pub.ecdsa_pub_keys[i] for i in (0, 1, 2)]
        victim = validators[0]
        base_nodes = metrics.counter_value("fastsync_nodes_downloaded_total")
        base_fail = metrics.counter_value("fastsync_failovers_total")

        task = asyncio.create_task(fs.sync(serving, timeout=60))
        # kill one serving peer mid-download
        await _wait_counter("fastsync_nodes_downloaded_total", base_nodes, 2_000)
        _kill(victim)
        synced = await task
        assert synced == 1
        assert obs.block_manager.current_height() == 1
        _spot_check_balances(obs, 80_000)

        # failover really happened and the scoreboard shows the dead peer
        assert metrics.counter_value("fastsync_failovers_total") > base_fail
        vscore = fs.scoreboard[pub.ecdsa_pub_keys[0]]
        assert vscore.timeouts >= 1
        # healthy peers served; the labeled scoreboard is scrapeable
        served = metrics.counters_with_prefix("fastsync_peer_served_total")
        labels = {dict(k[1]).get("peer") for k in served}
        assert pub.ecdsa_pub_keys[1].hex()[:16] in labels
        # the frontier stayed bounded and left no KV residue
        assert fs._frontier.peak <= fs.frontier_cap
        assert (
            list(obs.kv.scan_prefix(prefixed(EntryPrefix.FASTSYNC_FRONTIER)))
            == []
        )

        # the synced store passes a deep integrity scan
        from lachain_tpu.storage.fsck import fsck

        report = fsck(obs.kv, repair=True, deep=True)
        assert not report.fatal, report.to_dict()

        # ... and the node then follows real consensus from the survivors
        obs.start_services()
        await asyncio.gather(*(v.run_era(2) for v in validators[1:]))
        await obs.synchronizer.wait_for_height(2, timeout=30)
        assert (
            obs.block_manager.block_by_height(2).hash()
            == validators[1].block_manager.block_by_height(2).hash()
        )
        await _stop_all(validators + [obs])

    asyncio.run(main())


def test_stale_and_duplicate_replies_never_consumed():
    """Regression for the late-reply race: replies from abandoned or
    duplicated exchanges are dropped by request-id bookkeeping — they can
    never be consumed as the current batch's answer."""
    pub, privs = trusted_key_gen(4, 1, rng=Rng(41))

    async def main():
        _t, _b, _r, servers = await _cluster(
            pub, privs, accounts=1_500, n_servers=1
        )
        obs = await _observer(pub, seed=77)
        _join(obs, servers)
        fs = obs.fast_sync
        srv = servers[0]
        spub = pub.ecdsa_pub_keys[0]
        base = metrics.counter_value("fastsync_stale_replies_total")
        # a legacy id-less reply (the kind the old client consumed blindly)
        fs._on_trie_nodes_reply(spub, [b"garbage"])
        # a reply for a request id this client never issued
        fs._on_trie_nodes_reply_id(spub, 424242, [b"garbage"])
        assert (
            metrics.counter_value("fastsync_stale_replies_total") == base + 2
        )

        # server answers every request TWICE: the duplicate must be dropped
        orig = srv.fast_sync._serve_trie_nodes_id

        def duplicate_serve(sender, rid, hashes):
            orig(sender, rid, hashes)
            orig(sender, rid, hashes)

        srv.network.on_trie_nodes_request_id = duplicate_serve
        synced = await fs.sync(spub, timeout=30)
        assert synced == 1
        _spot_check_balances(obs, 1_500)
        # the duplicates were all counted stale, and nothing was mistaken
        # for another batch (the sync completed with correct state)
        assert (
            metrics.counter_value("fastsync_stale_replies_total") > base + 2
        )
        await _stop_all(servers + [obs])

    asyncio.run(main())


def test_poisoning_peer_banned_sync_completes():
    """A peer serving nodes that do not hash to their request is banned for
    the session; the download completes from the honest peers."""
    pub, privs = trusted_key_gen(4, 1, rng=Rng(51))

    async def main():
        _t, _b, _r, servers = await _cluster(
            pub, privs, accounts=3_000, n_servers=3
        )
        obs = await _observer(pub, seed=78)
        _join(obs, servers)
        fs = obs.fast_sync
        fs.request_timeout = 1.0
        poisoner = servers[0]
        poison_pub = pub.ecdsa_pub_keys[0]

        def poison(sender, rid, hashes):
            poisoner.network.send_to(
                sender,
                wire.trie_nodes_reply_id(
                    rid, [b"poisoned-node-%d" % i for i in range(len(hashes))]
                ),
            )

        poisoner.network.on_trie_nodes_request_id = poison
        base_ban = metrics.counter_value(
            "fastsync_peer_banned_total",
            labels={"peer": poison_pub.hex()[:16]},
        )
        synced = await fs.sync(
            [pub.ecdsa_pub_keys[i] for i in range(3)], timeout=30
        )
        assert synced == 1
        _spot_check_balances(obs, 3_000)
        assert fs.scoreboard[poison_pub].banned
        assert fs.scoreboard[poison_pub].bad_nodes > 0
        assert (
            metrics.counter_value(
                "fastsync_peer_banned_total",
                labels={"peer": poison_pub.hex()[:16]},
            )
            == base_ban + 1
        )
        # no poisoned bytes made it into the store: deep-check the tip trie
        from lachain_tpu.storage.fsck import fsck

        assert not fsck(obs.kv, repair=True, deep=True).fatal
        await _stop_all(servers + [obs])

    asyncio.run(main())


def test_sync_aborts_only_when_no_peer_remains():
    """Graceful degradation bound: the download keeps going while ANY peer
    serves, and fails with a clear error only when none remain."""
    pub, privs = trusted_key_gen(4, 1, rng=Rng(61))

    async def main():
        _t, _b, _r, servers = await _cluster(
            pub, privs, accounts=6_000, n_servers=2
        )
        obs = await _observer(pub, seed=79)
        _join(obs, servers)
        fs = obs.fast_sync
        fs.request_timeout = 0.3
        fs.peer_death_threshold = 2
        base = metrics.counter_value("fastsync_nodes_downloaded_total")
        task = asyncio.create_task(
            fs.sync([pub.ecdsa_pub_keys[0], pub.ecdsa_pub_keys[1]], timeout=30)
        )
        await _wait_counter("fastsync_nodes_downloaded_total", base, 256)
        for s in servers:
            _kill(s)
        with pytest.raises(ValueError, match="no live serving peers remain"):
            await task
        await _stop_all(servers + [obs])

    asyncio.run(main())


def test_frontier_bounded_during_sync():
    """The BFS frontier's resident size never exceeds the cap on a trie far
    wider than the cap; the overflow spills through the KV and is cleaned
    up on completion."""
    pub, privs = trusted_key_gen(4, 1, rng=Rng(71))

    async def main():
        _t, _b, _r, servers = await _cluster(
            pub, privs, accounts=4_000, n_servers=2
        )
        obs = await _observer(pub, seed=80)
        _join(obs, servers)
        fs = obs.fast_sync
        fs.frontier_cap = 128
        synced = await fs.sync(
            [pub.ecdsa_pub_keys[0], pub.ecdsa_pub_keys[1]], timeout=30
        )
        assert synced == 1
        assert fs._frontier.peak <= 128
        assert fs._frontier.spilled_total > 0  # the cap actually bit
        assert (
            list(obs.kv.scan_prefix(prefixed(EntryPrefix.FASTSYNC_FRONTIER)))
            == []
        )
        _spot_check_balances(obs, 4_000)
        await _stop_all(servers + [obs])

    asyncio.run(main())


def test_two_run_outcome_determinism_under_seeded_faults():
    """Two observers syncing under the same seeded FaultPlan (15% frame
    loss) converge on identical state: same height, same roots, and the
    same downloaded-node count (each missing node is stored exactly once,
    however many retries the loss forces)."""
    pub, privs = trusted_key_gen(4, 1, rng=Rng(81))

    async def main():
        _t, block, roots, servers = await _cluster(
            pub, privs, accounts=3_000, n_servers=2
        )
        peers = [pub.ecdsa_pub_keys[0], pub.ecdsa_pub_keys[1]]
        outcomes = []
        for run, seed in enumerate((91, 92)):
            obs = await _observer(pub, seed=seed)
            _join(obs, servers)
            plan = FaultPlan(seed=5, drop=0.15)
            obs.network.hub.frame_filter = TcpFrameFilter(
                plan.session(salt=3), my_id=0
            )
            fs = obs.fast_sync
            fs.request_timeout = 0.5
            base = metrics.counter_value("fastsync_nodes_downloaded_total")
            synced = await fs.sync(peers, timeout=10)
            downloaded = (
                metrics.counter_value("fastsync_nodes_downloaded_total") - base
            )
            outcomes.append(
                (synced, obs.state.committed.state_hash(), downloaded)
            )
            assert not any(s.banned for s in fs.scoreboard.values())
            _spot_check_balances(obs, 3_000)
            await obs.stop()
        assert outcomes[0] == outcomes[1]
        assert outcomes[0][1] == block.header.state_hash
        await _stop_all(servers)

    asyncio.run(main())


def test_snapshot_sync_resumes_across_peer_kill():
    """--snapshot bulk path: cursor-paged pull imports the whole trie
    keyspace; killing the serving peer mid-stream resumes at the same
    cursor from the survivor, and the verifying walk then has (almost)
    nothing left to download."""
    pub, privs = trusted_key_gen(4, 1, rng=Rng(101))

    async def main():
        _t, _b, _r, servers = await _cluster(
            pub, privs, accounts=20_000, n_servers=2
        )
        obs = await _observer(pub, seed=81)
        _join(obs, servers)
        fs = obs.fast_sync
        fs.request_timeout = 1.0
        fs.snapshot_page = 2_048
        base_pages = metrics.counter_value("fastsync_snapshot_pages_total")
        base_nodes = metrics.counter_value("fastsync_nodes_downloaded_total")
        base_fail = metrics.counter_value("fastsync_failovers_total")
        task = asyncio.create_task(
            fs.sync(
                [pub.ecdsa_pub_keys[0], pub.ecdsa_pub_keys[1]],
                timeout=30,
                snapshot=True,
            )
        )
        await _wait_counter("fastsync_snapshot_pages_total", base_pages, 3)
        _kill(servers[0])
        synced = await task
        assert synced == 1
        _spot_check_balances(obs, 20_000)
        # the bulk path carried the state: the walk downloaded ~nothing
        assert (
            metrics.counter_value("fastsync_nodes_downloaded_total") - base_nodes
            < 1_000
        )
        assert metrics.counter_value("fastsync_failovers_total") > base_fail
        await _stop_all(servers + [obs])

    asyncio.run(main())


def test_snapshot_falls_back_to_node_by_node():
    """Peers that serve no snapshot records degrade the bulk path into the
    plain verified walk — same final state, no penalty spiral."""
    pub, privs = trusted_key_gen(4, 1, rng=Rng(111))

    async def main():
        _t, _b, _r, servers = await _cluster(
            pub, privs, accounts=1_000, n_servers=2
        )
        for srv in servers:
            def empty_snapshot(sender, rid, cursor, limit, _srv=srv):
                _srv.network.send_to(
                    sender, wire.snapshot_reply(rid, cursor, False, [])
                )

            srv.network.on_snapshot_request = empty_snapshot
        obs = await _observer(pub, seed=82)
        _join(obs, servers)
        base_rec = metrics.counter_value("fastsync_snapshot_records_total")
        base_nodes = metrics.counter_value("fastsync_nodes_downloaded_total")
        synced = await obs.fast_sync.sync(
            [pub.ecdsa_pub_keys[0], pub.ecdsa_pub_keys[1]],
            timeout=30,
            snapshot=True,
        )
        assert synced == 1
        _spot_check_balances(obs, 1_000)
        assert (
            metrics.counter_value("fastsync_snapshot_records_total")
            == base_rec
        )
        assert (
            metrics.counter_value("fastsync_nodes_downloaded_total") - base_nodes
            > 1_000
        )
        await _stop_all(servers + [obs])

    asyncio.run(main())


def test_serve_throttle_bounds_kv_work():
    """The serving side meters requests in NODE units through a per-sender
    token bucket: oversized bursts are dropped (and counted), across all
    three serving kinds."""
    pub, privs = trusted_key_gen(4, 1, rng=Rng(121))

    async def main():
        node = Node(
            index=0, public_keys=pub, private_keys=privs[0],
            chain_id=CHAIN, initial_balances={}, flush_interval=0.01,
        )
        sent = []
        node.network.send_to = lambda pub_, msg: sent.append((pub_, msg))
        fs = node.fast_sync
        fs.serve_rate = 0.0  # no refill: the budget is exactly the capacity
        fs.serve_capacity = 10.0
        throttled = lambda: metrics.counter_value(  # noqa: E731
            "fastsync_serve_throttled_total"
        )
        base = throttled()
        h = b"\x01" * 32
        fs._serve_trie_nodes_id(b"peerA", 1, [h] * 20)  # cost 20 > 10
        assert sent == [] and throttled() == base + 1
        fs._serve_trie_nodes_id(b"peerA", 2, [h] * 5)  # within budget
        assert len(sent) == 1
        fs._serve_trie_nodes_id(b"peerA", 3, [h] * 8)  # 5 tokens left < 8
        assert len(sent) == 1 and throttled() == base + 2
        # the legacy kind and the snapshot pager ride the same buckets
        fs._serve_trie_nodes(b"peerB" + b"\x00" * 28, [h] * 20)
        assert len(sent) == 1 and throttled() == base + 3
        fs._serve_snapshot(b"peerC" + b"\x00" * 28, 1, b"", 50)
        assert len(sent) == 1 and throttled() == base + 4

    asyncio.run(main())


def test_bounded_frontier_unit():
    """BoundedFrontier contract: resident size <= cap, spill rows live under
    FASTSYNC_FRONTIER and vanish on restore/clear, every pushed hash pops
    exactly once, requeue bypasses dedup."""
    from lachain_tpu.core.fast_sync import BoundedFrontier

    kv = MemoryKV()
    fr = BoundedFrontier(kv, cap=64, chunk=16)
    hashes = [i.to_bytes(32, "big") for i in range(1_000)]
    for h in hashes:
        fr.push(h)
        assert len(fr._mem) <= 64
    assert len(fr) == 1_000
    assert fr.peak <= 64
    assert fr.spilled_total > 0
    spill_rows = list(kv.scan_prefix(prefixed(EntryPrefix.FASTSYNC_FRONTIER)))
    assert spill_rows  # overflow actually went to the KV
    # duplicate pushes are absorbed by the seen-set
    fr.push(hashes[0])
    assert len(fr) == 1_000
    popped = []
    while True:
        got = fr.pop_many(100)
        if not got:
            break
        popped.extend(got)
        assert len(fr._mem) <= 64 + 100  # restore refills by chunk
    assert sorted(popped) == sorted(hashes)  # each exactly once
    # requeue (the retry path) bypasses dedup
    fr.requeue(hashes[:3])
    assert fr.pop_many(10) == hashes[:3]
    fr.clear()
    assert (
        list(kv.scan_prefix(prefixed(EntryPrefix.FASTSYNC_FRONTIER))) == []
    )


@pytest.mark.slow
def test_fast_sync_survives_real_sigkill():
    """The slow-marked variant of the failover proof: serving peers are real
    OS processes; one is SIGKILLed mid-download and the observer finishes
    from the survivor."""
    import json
    import os
    import signal
    import subprocess
    import sys

    n, f, key_seed, accounts = 4, 1, 11, 20_000
    pub, _privs = trusted_key_gen(n, f, rng=Rng(key_seed))

    def spawn(index):
        code = (
            "from lachain_tpu.core.devnet import run_fixture_server; "
            f"run_fixture_server(n={n}, f={f}, index={index}, "
            f"seed={key_seed}, fixture_seed={FIXTURE_SEED}, "
            f"accounts={accounts}, chain_id={CHAIN})"
        )
        return subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.PIPE,
            env=dict(os.environ, JAX_PLATFORMS="cpu", LOG_LEVEL="WARNING"),
        )

    procs = [spawn(0), spawn(1)]
    try:
        addrs = []
        for i, p in enumerate(procs):
            line = p.stdout.readline()
            info = json.loads(line)
            assert bytes.fromhex(info["pub"]) == pub.ecdsa_pub_keys[i]
            from lachain_tpu.network.hub import PeerAddress

            addrs.append(
                PeerAddress(
                    public_key=bytes.fromhex(info["pub"]),
                    host="127.0.0.1",
                    port=info["port"],
                )
            )

        async def main():
            obs = await _observer(pub, seed=83)
            obs.connect(addrs)
            fs = obs.fast_sync
            fs.request_timeout = 1.0
            base = metrics.counter_value("fastsync_nodes_downloaded_total")
            task = asyncio.create_task(
                fs.sync([a.public_key for a in addrs], timeout=60)
            )
            await _wait_counter("fastsync_nodes_downloaded_total", base, 2_000)
            os.kill(procs[0].pid, signal.SIGKILL)
            synced = await task
            assert synced == 1
            _spot_check_balances(obs, accounts)
            vscore = fs.scoreboard[addrs[0].public_key]
            assert vscore.timeouts >= 1 or vscore.dead
            await obs.stop()

        asyncio.run(main())
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
