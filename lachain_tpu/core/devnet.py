"""Single-process multi-validator devnet — the end-to-end slice.

Parity with the reference's 4-node local net (docker-compose.4nodes.yml +
TrustedKeygen, SURVEY.md §4.5) collapsed into one process for tests and the
bench: N validators, each with its own KV store / state / pool / producer,
wired through the deterministic simulator. The era loop plays the role of
ConsensusManager.Run (/root/reference/src/Lachain.Core/Consensus/
ConsensusManager.cs:191-360): start RootProtocol for era E, wait for every
node's block, verify they all committed the same block, advance.
"""
from __future__ import annotations

import random
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from ..consensus import messages as M
from ..consensus.keys import trusted_key_gen
from ..consensus.root_protocol import RootProtocol
from ..consensus.simulator import DeliveryMode, SimulatedNetwork
from ..crypto import ecdsa
from ..crypto.hashes import keccak256
from ..storage.kv import EntryPrefix, KVStore, MemoryKV, prefixed
from ..storage.state import StateManager
from ..utils.serialization import write_u64
from . import system_contracts
from .block_manager import BlockManager
from .block_producer import BlockProducer
from .execution import get_balance, set_balance
from .tx_pool import StateNonces, TransactionPool
from .types import (
    ZERO_HASH,
    Block,
    BlockHeader,
    MultiSig,
    SignedTransaction,
)

DEFAULT_CHAIN_ID = 225  # our own chain id


@dataclass
class DevnetNode:
    index: int
    kv: KVStore
    state: StateManager
    block_manager: BlockManager
    pool: TransactionPool
    producer: BlockProducer


def devnet_keys(n: int, f: int, seed: int):
    """The committee's keys (trusted_key_gen) drawn from `seed`: the same
    for every harness given the same seed."""
    rng = random.Random(seed)

    class _Rng:
        def randbelow(self, k):
            return rng.randrange(k)

    return trusted_key_gen(n, f, rng=_Rng())


def make_node(
    i: int,
    kv: KVStore,
    public_keys,
    chain_id: int,
    initial_balances: Dict[bytes, int],
    txs_per_block: int,
) -> DevnetNode:
    """Validator i's store, state, chain from genesis, pool and producer."""
    state = StateManager(kv, native=True)
    # full system-contract registry (deploy/LRC-20/governance/staking)
    # so the devnet exercises the same execution surface as a real node
    executer = system_contracts.make_executer(chain_id)
    bm = BlockManager(kv, state, executer)
    bm.build_genesis(
        initial_balances,
        chain_id,
        validator_pubs=list(public_keys.ecdsa_pub_keys),
    )
    pool = TransactionPool(
        kv,
        chain_id,
        account_nonce=StateNonces(state),
    )
    producer = BlockProducer(
        bm, pool, public_keys.n, txs_per_block, proposal_seed=i
    )
    return DevnetNode(
        index=i,
        kv=kv,
        state=state,
        block_manager=bm,
        pool=pool,
        producer=producer,
    )


class Devnet:
    """N-validator in-process chain with HoneyBadger consensus."""

    def __init__(
        self,
        n: int = 4,
        f: int = 1,
        chain_id: int = DEFAULT_CHAIN_ID,
        seed: int = 0,
        txs_per_block: int = 1000,
        initial_balances: Optional[Dict[bytes, int]] = None,
        mode: DeliveryMode = DeliveryMode.TAKE_FIRST,
        engine: str = "python",
        fault_plan=None,
        max_recovery_rounds: int = 16,
        kv_factory: Optional[Callable[[int], KVStore]] = None,
        pipeline_window: int = 0,
        journals: Optional[List] = None,
        adversary=None,
        link_shaper=None,
        rbc_batch: bool = False,
    ):
        # link_shaper (network/faults.py LinkShaper): WAN emulation on the
        # simulated delivery layer — per-region-pair latency/jitter/
        # bandwidth in virtual ticks. A convenience over threading a full
        # FaultPlan: wraps into one (or onto the given plan) here.
        if link_shaper is not None:
            import dataclasses as _dc

            from ..network.faults import FaultPlan

            if fault_plan is None:
                fault_plan = FaultPlan(seed=seed, shaper=link_shaper)
            else:
                fault_plan = _dc.replace(fault_plan, shaper=link_shaper)
        self.n, self.f = n, f
        self.chain_id = chain_id
        # pipeline_window > 0 turns run_eras into a windowed scheduler that
        # overlaps era e+1's front (propose/RBC/BA/coin/TPKE) with era e's
        # tail (sign/verify/commit) — native engine only
        self.pipeline_window = max(int(pipeline_window), 0)
        if self.pipeline_window > 0 and engine != "native":
            raise ValueError("era pipelining requires engine='native'")
        self.public_keys, self.private_keys = devnet_keys(n, f, seed)
        self.initial_balances = dict(initial_balances or {})

        # kv_factory(node_index) -> KVStore lets campaigns run each
        # validator on a DURABLE engine (LsmKV/SqliteKV store per node)
        # instead of the default in-memory store — the state-root identity
        # tests drive the same devnet over both engines this way
        self.nodes: List[DevnetNode] = [
            make_node(
                i,
                kv_factory(i) if kv_factory is not None else MemoryKV(),
                self.public_keys,
                chain_id,
                self.initial_balances,
                txs_per_block,
            )
            for i in range(n)
        ]

        def root_factory_for(node: DevnetNode):
            def factory(pid, router):
                return RootProtocol(
                    pid,
                    router,
                    producer=node.producer,
                    ecdsa_priv=self.private_keys[node.index].ecdsa_priv,
                    ecdsa_pubs=self.public_keys.ecdsa_pub_keys,
                )

            return factory

        # one shared simulated network; per-node RootProtocol factories.
        # engine="native" routes the flood protocols through the C++ runtime
        # (consensus/native_rt.py) — same protocols, same crypto, ~100x the
        # dispatch throughput at N=64.
        # fault_plan (network/faults.py FaultPlan) threads through to the
        # delivery layer: chaos tests and the `lachain-tpu chaos` verb run
        # whole eras under seeded loss/partition/crash schedules
        if engine == "native":
            from ..consensus.native_rt import NativeSimulatedNetwork

            net_cls = NativeSimulatedNetwork
            net_kw = dict(
                fault_plan=fault_plan,
                pipeline_window=self.pipeline_window,
                journals=journals,
                use_rbc_batcher=rbc_batch,
            )
        else:
            net_cls = SimulatedNetwork
            net_kw = dict(
                fault_plan=fault_plan,
                max_recovery_rounds=max_recovery_rounds,
                use_rbc_batcher=rbc_batch,
            )
            if journals is not None:
                # the python simulator has no journal hosting; passing one
                # is a real request we cannot honor silently
                raise ValueError(
                    "consensus journals require engine='native'"
                )
        self.net = net_cls(
            self.public_keys,
            self.private_keys,
            era=1,
            seed=seed,
            mode=mode,
            **net_kw,
        )
        for i, router in enumerate(self.net.routers):
            if engine == "native":
                # native engine: hand each validator its block-production
                # context so RootProtocol is hosted natively (an
                # _extra_factories override still forces the Python class)
                self.net.set_root_context(
                    i,
                    self.nodes[i].producer,
                    self.private_keys[i].ecdsa_priv,
                    self.public_keys.ecdsa_pub_keys,
                )
            else:
                router._extra_factories[M.RootProtocolId] = root_factory_for(
                    self.nodes[i]
                )
        # adversary (consensus/adversary.py AdversaryPlan): smart-malicious
        # traitors with real key shares. Installed AFTER root contexts so a
        # native traitor's python-override fallback finds its producer seam.
        self.adversary = adversary
        if adversary is not None:
            from ..consensus.adversary import install as install_adversary

            install_adversary(adversary, self.net)

    # -- tx ingress -------------------------------------------------------------
    def submit_tx(self, stx: SignedTransaction, to_node: int = 0) -> bool:
        """Reference path: eth_sendRawTransaction -> TransactionPool.Add; the
        devnet gossips the tx to every node's pool (BroadcastLocalTransaction
        role)."""
        from ..utils import tracing, txtrace

        # between eras, so outside every `era` span: the hand-over of load
        with tracing.span("devnet.submit_tx", "pool"):
            txtrace.stamp(stx.hash(), "submit")
            ok = self.nodes[to_node].pool.add(stx)
            if ok:
                for node in self.nodes:
                    if node.index != to_node:
                        node.pool.add(stx)
            return ok

    # -- era loop ----------------------------------------------------------------
    def run_era(self, era: int, max_messages: int = 2_000_000) -> List[Block]:
        """Run one consensus era to completion on every node."""
        from ..utils import tracing

        # the era span is the flight recorder's attribution window: the
        # era report and the clock-alignment tests anchor on it
        with tracing.span("era", era=era):
            with tracing.span("era.advance", "engine", era=era):
                for router in self.net.routers:
                    router.advance_era(era)
            pid = M.RootProtocolId(era=era)
            for i in range(self.n):
                self.net.post_request(i, pid, None)
            ok = self.net.run(
                lambda: all(
                    r.result_of(pid) is not None for r in self.net.routers
                ),
                max_messages=max_messages,
            )
        if not ok:
            raise RuntimeError(f"era {era} did not complete")
        blocks = [r.result_of(pid) for r in self.net.routers]
        h0 = blocks[0].hash()
        assert all(b.hash() == h0 for b in blocks), "devnet fork!"
        return blocks

    def run_eras(
        self, first: int, count: int, max_messages: int = 2_000_000
    ) -> List[Block]:
        if self.pipeline_window > 0:
            return self._run_eras_pipelined(
                first, count, max_messages=max_messages
            )
        out = []
        for era in range(first, first + count):
            out.append(self.run_era(era, max_messages=max_messages)[0])
        return out

    # -- pipelined era window ---------------------------------------------------
    def _decided_txs(self, era: int) -> List[SignedTransaction]:
        """The tx set era `era`'s block WILL carry, derived from router 0's
        HB result exactly as RootHost.on_sign derives it (the result is
        content-identical at every validator, so router 0 suffices).
        Available at front-complete — before the block itself exists."""
        from .block_producer import decode_tx_batch

        hb_result = self.net.routers[0].hb_host(era).result or {}
        seen = set()
        txs: List[SignedTransaction] = []
        for slot in sorted(hb_result):
            try:
                batch = decode_tx_batch(hb_result[slot])
            except (ValueError, AssertionError):
                continue
            for stx in batch:
                h = stx.hash()
                if h not in seen:
                    seen.add(h)
                    txs.append(stx)
        return txs

    def _run_eras_pipelined(
        self, first: int, count: int, max_messages: int = 2_000_000
    ) -> List[Block]:
        """Windowed era scheduler: era e+1's FRONT (propose/encrypt/RBC/BA/
        coin/TPKE verify-combine, up to the deferred header sign) runs on
        this thread while era e's TAIL (sign/flood/ECDSA-verify/produce/
        commit) runs on a worker thread. Commits stay strictly sequential
        (the tail worker processes eras ascending), so state roots — and
        block hashes — are exactly the sequential run's. At most
        pipeline_window + 1 eras are in flight at once."""
        import queue as queue_mod
        import threading

        from ..utils import metrics, tracing

        window = self.pipeline_window
        eras = list(range(first, first + count))
        self.net.pipeline_begin()
        committed = {e: threading.Event() for e in eras}
        blocks: Dict[int, Block] = {}
        era_spans: Dict[int, int] = {}
        tail_q: "queue_mod.Queue" = queue_mod.Queue()
        tail_err: List[BaseException] = []

        def tail_worker() -> None:
            while True:
                era = tail_q.get()
                if era is None:
                    return
                try:
                    with tracing.span("era.tail", era=era):
                        era_blocks = self.net.run_tail(
                            era, max_messages=max_messages
                        )
                        h0 = era_blocks[0].hash()
                        assert all(
                            b.hash() == h0 for b in era_blocks
                        ), "devnet fork!"
                        self.net.commit_era(era)
                    blocks[era] = era_blocks[0]
                    tracing.end(era_spans[era])
                    committed[era].set()
                except BaseException as exc:  # noqa: BLE001
                    tail_err.append(exc)
                    committed[era].set()  # unblock the scheduler
                    return

        worker = threading.Thread(
            target=tail_worker, name="consensus-tail", daemon=True
        )
        worker.start()
        in_flight: List[int] = []
        try:
            for era in eras:
                # admission: keep at most window fronts ahead of the
                # oldest uncommitted era
                while len(in_flight) > window:
                    committed[in_flight[0]].wait()
                    if tail_err:
                        raise tail_err[0]
                    in_flight.pop(0)
                if tail_err:
                    raise tail_err[0]
                # the "era" span opens at admission and closes at commit
                # (on the tail thread): neighbor eras' spans genuinely
                # overlap, which is what era_report's overlap_s measures
                era_spans[era] = tracing.begin("era", era=era)
                self.net.open_era(era)
                pid = M.RootProtocolId(era=era)
                for i in range(self.n):
                    self.net.post_request(i, pid, None)
                with tracing.span("era.front", era=era):
                    self.net.run_front(era, max_messages=max_messages)
                in_flight.append(era)
                metrics.set_gauge("consensus_pipeline_depth", len(in_flight))
                if era != eras[-1]:
                    # before era+1 proposes: overlay this era's decided tx
                    # set so the next proposal behaves as if the block had
                    # already committed (main thread — the overlay is only
                    # read here, by the next post_request's proposal)
                    txs = self._decided_txs(era)
                    for node in self.nodes:
                        node.producer.pipeline_overlay_push(
                            era, txs, self.chain_id
                        )
                tail_q.put(era)
            for era in in_flight:
                committed[era].wait()
                if tail_err:
                    raise tail_err[0]
        finally:
            tail_q.put(None)
            worker.join(timeout=60)
            metrics.set_gauge("consensus_pipeline_depth", 0)
            for node in self.nodes:
                node.producer.pipeline_overlay_clear()
            self.net.pipeline_end()
        return [blocks[e] for e in eras]

    # -- helpers ------------------------------------------------------------------
    def close(self) -> None:
        """Release per-node stores (no-op for MemoryKV; required for the
        durable engines a kv_factory may supply) and the native engine,
        which is otherwise left to the collector."""
        for node in self.nodes:
            node.kv.close()
        self.net.close()

    def balance(self, addr: bytes, node: int = 0) -> int:
        return get_balance(self.nodes[node].state.new_snapshot(), addr)

    def height(self, node: int = 0) -> int:
        return self.nodes[node].block_manager.current_height()


class CommitteeValidator:
    """Validator 0 of an N-member committee whose other N-1 members are
    hosted elsewhere and reach it only as messages (`committee`, e.g.
    consensus/committee_script.py): its own store, pool and producer, the
    native engine with the TPKE and RBC era batchers, over a network that
    hosts it alone. Devnet's era loop for one validator: a block is
    committed when this validator has persisted it."""

    def __init__(
        self,
        public_keys,
        private_keys,
        committee,
        *,
        chain_id: int = DEFAULT_CHAIN_ID,
        seed: int = 0,
        txs_per_block: int = 1000,
        initial_balances: Optional[Dict[bytes, int]] = None,
        rbc_batch: bool = True,
        kv: Optional[KVStore] = None,
    ):
        from ..consensus.native_rt import NativeSimulatedNetwork

        self.n, self.f = public_keys.n, public_keys.f
        self.chain_id = chain_id
        self.public_keys = public_keys
        self.node = make_node(
            0,
            kv if kv is not None else MemoryKV(),
            public_keys,
            chain_id,
            dict(initial_balances or {}),
            txs_per_block,
        )
        self.net = NativeSimulatedNetwork(
            public_keys,
            [private_keys],
            era=1,
            seed=seed,
            use_rbc_batcher=rbc_batch,
            committee=committee,
        )
        self.router = self.net.routers[0]
        self.net.set_root_context(
            0,
            self.node.producer,
            private_keys.ecdsa_priv,
            public_keys.ecdsa_pub_keys,
        )

    def submit_tx(self, stx: SignedTransaction) -> bool:
        """A client's transaction, to this validator's pool alone."""
        from ..utils import tracing, txtrace

        with tracing.span("devnet.submit_tx", "pool"):
            txtrace.stamp(stx.hash(), "submit")
            return self.node.pool.add(stx)

    def run_era(self, era: int, max_messages: int = 2_000_000) -> Block:
        """One era to this validator's committed block."""
        from ..utils import tracing

        pid = M.RootProtocolId(era=era)
        with tracing.span("era", era=era):
            with tracing.span("era.advance", "engine", era=era):
                self.router.advance_era(era)
            self.net.post_request(0, pid, None)
            ok = self.net.run(
                lambda: self.router.result_of(pid) is not None,
                max_messages=max_messages,
            )
        if not ok:
            raise RuntimeError(f"era {era} did not complete")
        return self.router.result_of(pid)

    def close(self) -> None:
        self.node.kv.close()
        self.net.close()


# -- fast-sync fixtures -------------------------------------------------------
# Deterministic chain fabrication for the state-download tests: a genesis +
# one properly multisigned block whose state trie carries an arbitrary number
# of synthetic accounts. Everything derives from (keys, seed, accounts), so
# the same fixture can be rebuilt bit-identically in another process — the
# real-SIGKILL fast-sync test runs serving validators as subprocesses that
# regenerate the exact same store from the same arguments.


def fixture_account(seed: int, i: int) -> bytes:
    """The i-th synthetic 20-byte address of a fabricated fixture."""
    return keccak256(b"devnet-fixture" + write_u64(seed) + write_u64(i))[:20]


def fabricate_chain_store(
    public_keys,
    private_keys,
    *,
    chain_id: int = DEFAULT_CHAIN_ID,
    accounts: int = 0,
    initial_balances: Optional[Dict[bytes, int]] = None,
    seed: int = 7,
    kv: Optional[KVStore] = None,
):
    """Genesis + a signed block 1 holding `accounts` synthetic balances.

    Returns (kv, block1, roots). The block carries an N-F validator
    multisig over its header, so a fast-syncing observer that knows the
    genesis validator set accepts it without a trusted checkpoint. The
    per-account addresses come from fixture_account(seed, i) — tests can
    spot-check balances without materializing the whole set.
    """
    kv = kv if kv is not None else MemoryKV()
    state = StateManager(kv)
    bm = BlockManager(kv, state, system_contracts.make_executer(chain_id))
    genesis = bm.build_genesis(
        dict(initial_balances or {}),
        chain_id,
        validator_pubs=list(public_keys.ecdsa_pub_keys),
    )
    snap = state.new_snapshot()
    for i in range(accounts):
        set_balance(snap, fixture_account(seed, i), 10_000 + i)
    roots = snap.freeze()
    header = BlockHeader(
        index=1,
        prev_block_hash=genesis.hash(),
        merkle_root=ZERO_HASH,
        state_hash=roots.state_hash(),
        nonce=0,
    )
    hh = header.hash()
    quorum = public_keys.n - public_keys.f
    sigs = tuple(
        (i, ecdsa.sign_hash(private_keys[i].ecdsa_priv, hh))
        for i in range(quorum)
    )
    block = Block(header=header, tx_hashes=(), multisig=MultiSig(sigs))
    kv.write_batch(
        [
            (prefixed(EntryPrefix.BLOCK_BY_HASH, block.hash()), block.encode()),
            (
                prefixed(EntryPrefix.BLOCK_HASH_BY_HEIGHT, write_u64(1)),
                block.hash(),
            ),
        ]
    )
    state.commit(1, roots)
    return kv, block, roots


def clone_store(src: KVStore, dst: Optional[KVStore] = None) -> KVStore:
    """Copy every row of `src` into `dst` (fresh MemoryKV by default).

    Fabricating a 100k-node fixture once and cloning it into each serving
    validator's store is an order of magnitude cheaper than rebuilding the
    trie per node — and content addressing makes the copies exact replicas.
    """
    dst = dst if dst is not None else MemoryKV()
    dst.ingest(list(src.scan_prefix(b"")))
    return dst


def run_fixture_server(
    *,
    n: int = 4,
    f: int = 1,
    index: int = 0,
    seed: int = 0,
    fixture_seed: int = 7,
    accounts: int = 0,
    chain_id: int = DEFAULT_CHAIN_ID,
    port: int = 0,
) -> None:
    """Subprocess entry point: serve a fabricated chain over real TCP.

    Regenerates the (deterministic) validator keys and fixture store from
    the same arguments the parent test used, starts a full Node on
    127.0.0.1, prints one JSON line {"port": ..., "pub": ...} so the parent
    can connect, then serves until killed — the parent SIGKILLs it
    mid-download to exercise real-process failover.
    """
    import asyncio
    import json
    import sys

    rng = random.Random(seed)

    class _Rng:
        def randbelow(self, k):
            return rng.randrange(k)

    public_keys, private_keys = trusted_key_gen(n, f, rng=_Rng())
    kv, _block, _roots = fabricate_chain_store(
        public_keys,
        private_keys,
        chain_id=chain_id,
        accounts=accounts,
        seed=fixture_seed,
    )

    async def _serve() -> None:
        from .node import Node

        node = Node(
            index=index,
            public_keys=public_keys,
            private_keys=private_keys[index],
            chain_id=chain_id,
            kv=kv,
            port=port,
            flush_interval=0.01,
        )
        # serving throughput is not what the failover tests measure: the
        # default serve throttle would read as timeouts on a hammering
        # observer and get the SURVIVOR declared dead
        node.fast_sync.serve_rate = 1e9
        node.fast_sync.serve_capacity = 1e9
        await node.start(start_synchronizer=False)
        print(
            json.dumps(
                {
                    "port": node.address.port,
                    "pub": node.address.public_key.hex(),
                }
            ),
            flush=True,
        )
        await asyncio.Event().wait()  # serve until the parent kills us

    try:
        asyncio.run(_serve())
    except KeyboardInterrupt:  # pragma: no cover - parent teardown
        sys.exit(0)
