"""A full networked node: consensus over TCP, pool gossip, era lifecycle.

Parity with the reference's node wiring
(/root/reference/src/Lachain.Core/Consensus/ConsensusManager.cs:191-360 era
loop + Application.Start:67-198 service composition): each validator runs a
NetworkManager (signed batches over the TCP hub), an EraRouter per era, a
TransactionPool with gossip (BroadcastLocalTransaction role,
NetworkManagerBase.cs:198-201), and produces blocks through RootProtocol.

The consensus data plane (batched share verification) still runs through
the JAX provider underneath the crypto layer; this module is host runtime.
"""
from __future__ import annotations

import asyncio
import logging
import time
from contextlib import nullcontext
from typing import Dict, List, Optional

from ..consensus import messages as M
from ..consensus.era import EraRouter
from ..consensus.keys import PrivateConsensusKeys, PublicConsensusKeys
from ..consensus.root_protocol import RootProtocol
from ..crypto import ecdsa
from ..network import wire
from ..network.hub import PeerAddress
from ..network.manager import NetworkManager
from ..storage.kv import EntryPrefix, KVStore, MemoryKV, prefixed
from ..storage.state import StateManager
from ..utils import tracing
from .block_manager import BlockManager
from .block_producer import BlockProducer
from .execution import TransactionExecuter
from .keygen_manager import KeyGenManager
from .synchronizer import BlockSynchronizer
from .tx_pool import StateNonces, TransactionPool
from .types import (
    Block,
    SignedTransaction,
    Transaction,
    sign_transaction,
    warm_sender_caches,
)
from .validator_manager import ValidatorManager
from .validator_status import ValidatorStatusManager
from .vault import PrivateWallet

logger = logging.getLogger(__name__)


class Node:
    """One validator/observer process."""

    def __init__(
        self,
        *,
        index: int,
        public_keys: PublicConsensusKeys,
        private_keys: PrivateConsensusKeys,
        chain_id: int,
        kv: Optional[KVStore] = None,
        host: str = "127.0.0.1",
        port: int = 0,
        txs_per_block: int = 1000,
        initial_balances: Optional[Dict[bytes, int]] = None,
        flush_interval: float = 0.02,
        executer: Optional[TransactionExecuter] = None,
        wallet: Optional[PrivateWallet] = None,
        block_interval: float = 0.0,
        advertise_host: Optional[str] = None,
        relay=None,  # "host:port:pubhex" or a list of them — NAT'd mode
        pipeline_window: int = 0,
    ):
        self.index = index
        # era-pipelining lookahead (config blockchain.pipelineWindow). On a
        # TCP node the window widens message acceptance and journal/GC
        # retention so pipelining peers (and the in-process devnet
        # scheduler) interoperate; the windowed front/tail overlap itself
        # is driven by the in-process scheduler (core/devnet.py).
        self.pipeline_window = max(int(pipeline_window), 0)
        self.public_keys = public_keys
        self.private_keys = private_keys
        self.chain_id = chain_id
        self.kv = kv if kv is not None else MemoryKV()
        # invariant scan BEFORE any subsystem reads the db: repairs the
        # safely-repairable torn states a crash can leave (orphan block
        # above tip, stale journal eras, undecodable pool rows) and
        # REFUSES to run on anything else — FsckError carries the report
        # (storage/fsck.py; DEPLOY.md "Crash recovery")
        from ..storage.fsck import FsckError, fsck

        self.fsck_report = fsck(self.kv, repair=True)
        if self.fsck_report.fatal:
            raise FsckError(self.fsck_report)
        self.state = StateManager(self.kv)
        from . import system_contracts

        self.block_manager = BlockManager(
            self.kv,
            self.state,
            executer or system_contracts.make_executer(chain_id),
        )
        # a store that holds a chain already: this process is a restart,
        # and times its way back into the committee (core/recovery.py).
        # None on a fresh store, so a node that never went away pays nothing
        from .recovery import RecoveryClock

        self.recovery: Optional[RecoveryClock] = (
            RecoveryClock(self.block_manager.current_height())
            if self.block_manager.block_by_height(0) is not None
            else None
        )
        self.block_manager.build_genesis(
            dict(initial_balances or {}),
            chain_id,
            validator_pubs=list(public_keys.ecdsa_pub_keys),
        )
        self.pool = TransactionPool(
            self.kv, chain_id, account_nonce=StateNonces(self.state)
        )
        # crash-restore: repopulate from the persisted pool repository (the
        # repository existed but was never replayed on open — a restart
        # silently lost every pending tx)
        with self._recover_span("pool") as sid:
            restored = self.pool.restore()
            tracing.annotate(sid, restored=restored)
        if restored:
            logger.info("restored %d pooled txs from disk", restored)
        # durable consensus send journal (consensus/journal.py): recovery
        # state re-armed in start(), rejoin requests sent in connect()
        from ..consensus.journal import ConsensusJournal

        self.journal = ConsensusJournal(self.kv)
        # durable Byzantine evidence (consensus/evidence.py): persisted on
        # the node KV before any counter publishes, queryable via
        # la_getEvidence, survives restart (fsck checks the records)
        from ..consensus.evidence import EvidenceStore

        self.evidence = EvidenceStore(self.kv)
        self._rejoin_eras: List[int] = []
        self.producer = BlockProducer(
            self.block_manager,
            self.pool,
            public_keys.n,
            txs_per_block,
            proposal_seed=max(index, 0),
        )
        self.network = NetworkManager(
            private_keys.ecdsa_priv,
            host,
            port,
            flush_interval=flush_interval,
            advertise_host=advertise_host,
            # the network only queues, so the fsync waits move to where it
            # writes to a socket, once a frame: not once a journal record,
            # and not once an admitted transaction
            barrier=self._frame_barrier(),
        )
        self._relay_spec = relay
        self.network.on_consensus = self._on_consensus
        self.network.on_sync_pool_reply = self._on_pool_txs
        self.network.on_ping_request = self._on_ping_request
        self.network.on_message_request = self._on_message_request
        # retransmission/recovery tuning (tests shrink these): the watchdog
        # sweeps every watchdog_interval; a protocol quiet for stall_timeout
        # escalates stall report -> outbox re-request -> forced reconnect
        self.watchdog_interval = 10.0
        self.stall_timeout = 60.0
        # WAN degradation: the ladder above is tuned for loopback; the
        # EFFECTIVE stall timeout stretches with observed fleet RTT
        # (network/rtt.py scale(): never below stall_timeout, capped at
        # 4x) so a 200 ms-RTT fleet degrades gracefully instead of
        # escalating to reconnect thrash on a loopback schedule
        # serving side: one outbox replay per (peer, era) per window, so a
        # hammering (or byzantine) requester cannot turn recovery into an
        # amplification attack
        self.replay_min_interval = 2.0
        # outbox replay batch cap, RTT-scaled upward on slow fleets (a
        # distant requester waits longer between requests, so each round
        # must carry more)
        self.replay_batch_limit = 512
        self._replay_served_at: Dict[tuple, float] = {}
        # native-engine stall detector state: (last_state_string, since, strikes)
        self._native_watch: tuple = ("", 0.0, 0)
        # health/SLO surface: last-commit clocks (monotonic for age math,
        # wall for display) seeded at boot so tip age counts from startup,
        # plus the highest watchdog escalation stage seen since the last
        # persisted block — forward progress clears the strike memory
        self._last_commit_mono = time.monotonic()
        self._last_commit_wall = time.time()
        self._stall_stage = 0
        # idle-anatomy alert (observability.idleAlertFraction): when set,
        # a rolling era idle fraction above it reads degraded on /healthz
        self.idle_alert_fraction: Optional[float] = None
        self.validator_manager = ValidatorManager(self.state, public_keys)
        from .fast_sync import FastSynchronizer

        # serving + client side of trie-level fast state sync; every node
        # serves (reference: peers answer state download RPCs)
        self.fast_sync = FastSynchronizer(self)
        self.synchronizer = BlockSynchronizer(
            self.block_manager,
            self.pool,
            self.network,
            public_keys,
            keys_provider=self.validator_manager.keys_for_era,
        )
        self.synchronizer.recovery = self.recovery
        # validator index <-> transport identity
        self._pub_by_index: Dict[int, bytes] = {
            i: pk for i, pk in enumerate(public_keys.ecdsa_pub_keys)
        }
        self._index_by_pub: Dict[bytes, int] = {
            pk: i for i, pk in self._pub_by_index.items()
        }
        self.router: Optional[EraRouter] = None
        self._era_done = asyncio.Event()
        self._stopping = False
        # (sender pubkey) -> [(era, payload)]: future-era consensus traffic
        self._future_msgs: Dict[bytes, list] = {}
        # -- autonomous lifecycle services (reference Application.Start
        #    wiring: KeyGenManager + ValidatorStatusManager hooked on block
        #    persistence; PrivateWallet holds era-keyed threshold keys) -----
        self.wallet = wallet or PrivateWallet(
            ecdsa_priv=private_keys.ecdsa_priv
        )
        self._genesis_private = private_keys
        self.ecdsa_pub = ecdsa.public_key_bytes(private_keys.ecdsa_priv)
        self.address20 = ecdsa.address_from_public_key(self.ecdsa_pub)
        self.keygen_manager = KeyGenManager(
            private_keys.ecdsa_priv,
            self._send_system_tx,
            on_keys=self._install_rotated_keys,
            kv=self.kv,
        )
        self.validator_status = ValidatorStatusManager(
            private_keys.ecdsa_priv,
            self._send_system_tx,
            # everyone who co-signed during that cycle — keyed by recorded
            # pubkeys, not the CURRENT set, so rotated-out validators'
            # attendance still gets reported
            attendance_reader=lambda cycle: self.attendance.counts_for(
                cycle
            ),
        )
        # per-cycle signed-header attendance, durable across restarts
        # (reference: ValidatorAttendance persisted from RootProtocol
        # signed headers, RootProtocol.cs:302-303 +
        # ValidatorAttendanceRepository)
        from ..consensus.attendance import ValidatorAttendance
        from . import system_contracts as _sc

        att_raw = self.kv.get(prefixed(EntryPrefix.VALIDATOR_ATTENDANCE))
        cur_cycle = self.block_manager.current_height() // _sc.CYCLE_DURATION
        if att_raw is not None:
            try:
                self.attendance = ValidatorAttendance.from_bytes(
                    att_raw, cur_cycle, current_as_next=False
                )
            except Exception:
                self.attendance = ValidatorAttendance(cur_cycle)
        else:
            self.attendance = ValidatorAttendance(cur_cycle)
        self.block_manager.on_block_persisted.append(self._on_block_persisted)
        self._height_event = asyncio.Event()
        # target era pacing for the autonomous loop (reference
        # TargetBlockTime, ConsensusManager.cs:78 — default 5000 ms there;
        # 0 = as fast as consensus completes, used by tests)
        self.block_interval = block_interval

    # -- service lifecycle --------------------------------------------------

    async def start(
        self, first_era: int = 1, *, start_synchronizer: bool = True
    ) -> None:
        """With start_synchronizer=False only the network comes up — the
        reference's fast-sync window (Application.Start runs
        FastSynchronizerBatch BEFORE blockSynchronizer.Start, so replay
        doesn't race the state download); call start_services() after."""
        await self.network.start()
        if self._relay_spec:
            # NAT'd mode (reference HubConnector bootstrap): register with
            # the configured relay(s); our gossip address becomes the relay
            # sentinel so peers route to us through it. A list enables
            # failover to the next relay when the current one goes dark.
            from ..network.hub import PeerAddress as _PA

            specs = (
                self._relay_spec
                if isinstance(self._relay_spec, (list, tuple))
                else [self._relay_spec]
            )
            relays = []
            for spec in specs:
                rhost, rport, rpub = spec.rsplit(":", 2)
                relays.append(
                    _PA(
                        public_key=bytes.fromhex(rpub),
                        host=rhost,
                        port=int(rport),
                    )
                )
            self.network.use_relay(relays)
        # the router exists before the era loop runs so consensus traffic
        # from faster peers is dispatched (or era-buffered), not dropped
        # (observers — index < 0 — only sync, never vote)
        if self.index >= 0:
            self._ensure_router(first_era)
            self._recover_journal()
        if self.recovery is not None:
            # listening: the peers' workers may dial this port again, and
            # the clock is told of the first verified frame from each
            peers = [
                pk for pk in self.public_keys.ecdsa_pub_keys
                if pk != self.network.public_key
            ]
            self.recovery.listening(len(peers))
            self.network.watch_first_frames(peers, self.recovery.peer_seen)
        if start_synchronizer:
            self.start_services()

    def _recover_span(self, step: str):
        """`node.recover.<step>`: a span around one step of opening a
        store that holds a chain; nothing on a fresh store."""
        if self.recovery is None:
            return nullcontext(0)
        return tracing.span(f"node.recover.{step}", cat="recover")

    def _recover_journal(self) -> None:
        """Crash-recovery replay (journal.py docstring): prune entries for
        eras already settled on-chain, re-arm the router's sent-latches and
        outbox from what remains, and remember the in-flight eras so
        connect() can rejoin them via message_request. Nothing is
        transmitted here — no peer workers exist yet."""
        assert self.router is not None
        height = self.block_manager.current_height()
        with self._recover_span("journal") as sid:
            self.journal.prune_below(height + 1)
            eras = set()
            n = 0
            for era, _seq, target, data in self.journal.entries():
                self.router.rearm_sent(era, target, data)
                eras.add(era)
                n += 1
            self._rejoin_eras = sorted(eras)
            tracing.annotate(sid, rearmed=n, eras=len(eras))
        if n:
            logger.info(
                "journal recovery: re-armed %d sends across eras %s",
                n,
                self._rejoin_eras,
            )

    def start_services(self) -> None:
        self.synchronizer.start()
        self._watchdog_task = asyncio.get_running_loop().create_task(
            self._protocol_watchdog()
        )
        # TPU backends: precompile the era-kernel shapes for this validator
        # set in the background so the first eras don't stall on tracing
        # and compiling them (crypto/warmup.py). Host backends: no-op.
        from ..crypto.warmup import warmup_era_kernels

        self._warmup_thread = warmup_era_kernels(self.public_keys.n)

    @property
    def effective_stall_timeout(self) -> float:
        """The watchdog's stall threshold, stretched with observed fleet
        RTT: base stall_timeout on fast links, up to 4x on slow ones
        (RttTracker.scale). Adaptivity widens patience; it never disables
        the ladder."""
        return self.network.rtt.scale(self.stall_timeout)

    async def _protocol_watchdog(self) -> None:
        """Protocol stall watchdog with last-message breadcrumb (reference
        AbstractProtocol 'taking too long' warnings, AbstractProtocol.cs:
        113-135) — escalating instead of merely reporting. Consensus never
        retransmits, so a stall that outlives one report is most likely a
        LOST message, not a slow peer: the second strike re-requests the
        era's traffic from every live peer (outbox replay), the third also
        forces the transport to drop cached sockets and re-dial."""
        import time as _time

        while not self._stopping:
            await asyncio.sleep(self.watchdog_interval)
            router = self.router
            if router is None:
                continue
            now = _time.monotonic()
            # natively-owned protocols have no python instance in
            # router._protocols — their only stall signal is the engine's
            # debug state; snapshot it once per sweep so every stall report
            # this sweep can name the engine side too
            native_state = ""
            nstate_fn = getattr(router, "native_state", None)
            if nstate_fn is not None:
                try:
                    native_state = nstate_fn()
                except Exception:  # engine may be torn down mid-sweep
                    native_state = "<unavailable>"
            # aggregate the ladder per era: one sweep re-requests/reconnects
            # once, however many of the era's protocols are stalled
            stall_after = self.effective_stall_timeout
            era_stage: Dict[int, int] = {}
            for pid, proto in list(router._protocols.items()):
                if proto.terminated or proto.result is not None:
                    continue
                stalled = now - proto.last_activity
                if stalled > stall_after:
                    stage = proto.record_stall()
                    logger.warning(
                        "protocol %s stalled for %.0fs (alive %.0fs, "
                        "strike %d, last message: %s, open spans: %s%s)",
                        pid,
                        stalled,
                        now - proto.started_at,
                        stage,
                        proto.last_message,
                        tracing.open_stack_str(),
                        f", native engine: {native_state}"
                        if native_state
                        else "",
                    )
                    tracing.instant(
                        "watchdog_stall",
                        cat="watchdog",
                        pid=str(pid),
                        stalled_s=round(stalled, 1),
                        stage=stage,
                        last_message=proto.last_message,
                        native_state=native_state,
                    )
                    proto.last_activity = now  # re-arm, don't spam
                    era = getattr(pid, "era", router.era)
                    era_stage[era] = max(era_stage.get(era, 0), stage)
            if nstate_fn is not None:
                stage = self._check_native_stall(router, native_state, now)
                if stage:
                    era_stage[router.era] = max(
                        era_stage.get(router.era, 0), stage
                    )
            for era, stage in era_stage.items():
                self._escalate_stall(era, stage)

    def _check_native_stall(self, router, native_state: str, now) -> int:
        """Stall detection for engine-hosted protocols: no python instance
        means no last_activity to age, so a natively-owned protocol id
        stalls silently unless the engine's debug state is watched. The
        state string encodes per-protocol progress (queue depths, epochs,
        inflight slots), so 'unchanged for stall_timeout while the era has
        no result' is the native analogue of a quiet protocol — report it
        naming the engine state and feed the same escalation ladder."""
        prev_state, mark, strikes = self._native_watch
        if native_state != prev_state or not native_state:
            self._native_watch = (native_state, now, 0)
            return 0
        if now - mark <= self.effective_stall_timeout:
            return 0
        # with pipelining the router spans a window of in-flight eras;
        # commits are strictly sequential, so the stuck era is the OLDEST
        # uncommitted one (window_floor), not the newest admitted
        stuck_era = router.era
        if self.pipeline_window > 0:
            stuck_era = getattr(router, "window_floor", router.era)
        if router.result_of(M.RootProtocolId(era=stuck_era)) is not None:
            # era complete on our side; quiet engine state is expected
            self._native_watch = (native_state, now, 0)
            return 0
        strikes += 1
        logger.warning(
            "native engine stalled for %.0fs in era %d (strike %d, "
            "engine state: %s)",
            now - mark,
            stuck_era,
            strikes,
            native_state,
        )
        tracing.instant(
            "watchdog_stall",
            cat="watchdog",
            pid=f"native:era{stuck_era}",
            stalled_s=round(now - mark, 1),
            stage=strikes,
            last_message="",
            native_state=native_state,
        )
        self._native_watch = (native_state, now, strikes)  # re-arm
        return strikes

    def _escalate_stall(self, era: int, stage: int) -> None:
        """Stage 2+: ask every live peer to replay its outbox for `era`
        (and replay our own outbox back at them — the loss may have been
        OUR message). Stage 3+: also force the transport to reconnect."""
        from ..utils import metrics

        self._stall_stage = max(self._stall_stage, stage)
        if stage < 2:
            return
        metrics.inc(
            "consensus_stall_escalations_total",
            labels={"stage": str(min(stage, 3))},
        )
        logger.warning(
            "era %d stalled (strike %d): re-requesting consensus traffic "
            "from %d peers",
            era,
            stage,
            len(self.network.peers),
        )
        self.network.broadcast(wire.message_request(era))
        if stage == 2 and self.router is not None and self.router.era == era:
            # push our own outbox once unprompted: the lost message may have
            # been OURS, and a peer wedged badly enough may never get its
            # own re-request out. Later strikes rely on the peers' replies
            # (re-pushing thousands of messages every sweep helps nobody).
            for idx, pub in self._pub_by_index.items():
                if idx == self.index:
                    continue
                for payload in self.router.outbox_payloads(era, idx):
                    self.network.send_to(pub, wire.consensus_msg(era, payload))
        if stage >= 3:
            self.network.reconnect_peers()

    def health(self) -> Dict[str, object]:
        """One-glance health verdict served by `GET /healthz` and
        `la_getHealth`. Three-state so load balancers and fleet dashboards
        can act without parsing the detail fields:

        ok       — committing, peered, no watchdog strikes
        degraded — behind the fleet's median height, peerless, tip older
                   than the (RTT-stretched) effective stall timeout, one
                   stall strike, or (when idle_alert_fraction is
                   configured) the rolling era idle fraction from the
                   flight recorder above it
        stalled  — watchdog escalated (strike >= 2, python or native) or
                   no commit for 2x the effective stall timeout
        """
        now = time.monotonic()
        tip_age = now - self._last_commit_mono
        height = self.block_manager.current_height()
        peer_heights = sorted(self.synchronizer.peer_heights.values())
        median_peer = (
            peer_heights[len(peer_heights) // 2] if peer_heights else height
        )
        lag = max(0, median_peer - height)
        strikes = max(self._stall_stage, self._native_watch[2])
        # peerless is only a symptom when peers are EXPECTED: a
        # single-validator devnet with nobody to dial stays "ok"
        expected_peers = max(0, len(self._pub_by_index) - 1)
        # rolling idle fraction over the last few completed eras in the
        # flight recorder; only computed when the alert is configured
        # (era_report sweeps the span ring — cheap, but not free)
        idle_fraction = None
        idle_alerting = False
        if self.idle_alert_fraction is not None:
            try:
                eras = tracing.era_report()["eras"][-3:]
                walls = sum(e["wall_s"] for e in eras)
                if walls > 0:
                    idle_fraction = round(
                        sum(e["idle_s"] for e in eras) / walls, 4
                    )
                    idle_alerting = idle_fraction > self.idle_alert_fraction
            except Exception:
                pass  # a recorder hiccup must never break the probe
        stall_after = self.effective_stall_timeout
        verdict = "ok"
        if (
            lag > 5
            or tip_age > stall_after
            or (expected_peers > 0 and not self.network.peers)
            or strikes == 1
            or idle_alerting
        ):
            verdict = "degraded"
        if strikes >= 2 or tip_age > 2 * stall_after:
            verdict = "stalled"
        return {
            "status": verdict,
            "height": height,
            "era": self.router.era if self.router is not None else None,
            "tipAgeSeconds": round(tip_age, 3),
            "lastCommitUnix": round(self._last_commit_wall, 3),
            "peerCount": len(self.network.peers),
            "poolDepth": len(self.pool),
            "medianPeerHeight": median_peer,
            "commitLagVsPeers": lag,
            "stallStrikes": strikes,
            "idleFraction": idle_fraction,
            # WAN surface: slowest-peer RTT estimate, the RTT-stretched
            # stall threshold in force, and our advertised wire version
            # (fleet dashboards watch the version column during a roll)
            "rttMaxMs": round(self.network.rtt.max_srtt() * 1000.0, 1),
            "stallTimeoutEffective": round(stall_after, 1),
            "wireVersion": self.network.factory.wire_version,
        }

    async def start_rpc(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        api_key: Optional[str] = None,
        auth_pubkey: Optional[str] = None,
    ):
        """Expose the Web3-shaped JSON-RPC surface (reference
        RpcManager.Start, RPC/RpcManager.cs:1-129). Returns the server
        (its .port reflects the bound port). `auth_pubkey` (compressed
        secp256k1 pubkey hex) unlocks the PRIVATE_METHODS family via
        timestamp+signature auth; when None they are refused."""
        from ..rpc import JsonRpcServer, RpcService

        server = JsonRpcServer(
            host, port, api_key=api_key, auth_pubkey=auth_pubkey
        )
        server.register_all(RpcService(self).methods())
        # liveness probes must work without credentials: the server special-
        # cases GET /healthz through this hook before its api-key gate
        server.health_fn = self.health
        await server.start()
        self._rpc_server = server
        return server

    async def stop(self) -> None:
        self._stopping = True
        self._height_event.set()
        if getattr(self, "_watchdog_task", None) is not None:
            self._watchdog_task.cancel()
            self._watchdog_task = None
        if getattr(self, "_rpc_server", None) is not None:
            await self._rpc_server.stop()
            self._rpc_server = None
        await self.synchronizer.stop()
        await self.network.stop()

    @property
    def address(self) -> PeerAddress:
        return self.network.address

    def connect(self, peers: List[PeerAddress]) -> None:
        for p in peers:
            self.network.add_peer(p)
        if self._rejoin_eras:
            # restart rejoin: ask every peer to replay the traffic of the
            # eras we were mid-flight in when we died (the watchdog's
            # escalation ladder is the backstop if this first ask is lost)
            from ..utils import metrics

            for era in self._rejoin_eras:
                self.network.broadcast(wire.message_request(era))
            metrics.inc(
                "consensus_rejoin_requests_total", len(self._rejoin_eras)
            )
            logger.info("rejoin: requested replay for eras %s", self._rejoin_eras)
            self._rejoin_eras = []

    def _frame_barrier(self):
        """What the network runs in front of every write to a socket
        (network/worker.durable_before_wire): whatever this node submitted
        to its store and a frame may carry or follow — the journal's
        records and the pool's admitted rows — is durable when it returns.
        Both ride the one WAL, so the second wait finds its ticket covered
        and returns at once. Either one raising holds the frame back."""
        journal_barrier = self.journal.frame_barrier()
        pool_barrier = self.pool.frame_barrier()

        def barrier() -> None:
            journal_barrier()
            pool_barrier()

        return barrier

    # -- tx ingress + gossip -----------------------------------------------

    def submit_tx(self, stx: SignedTransaction) -> bool:
        # tx lifecycle origin stamp: ingress accepted BEFORE pool admission
        # so the submit→pool delta measures admission, not transport
        from ..utils import txtrace

        txtrace.stamp(stx.hash(), "submit")
        # admitted to memory, its crash-restore row submitted: True is for
        # this process alone. Whoever passes it on waits for the pool's
        # barrier first — the network in front of the gossip's frame
        # (_frame_barrier), the RPC service in front of its answer
        with tracing.account("pool_admit"):
            ok = self.pool.add(stx)
        if ok:
            # encode once, enqueue on every peer's worker
            with tracing.account("gossip_out"):
                self.network.broadcast(wire.sync_pool_reply([stx]))
        return ok

    def _on_pool_txs(self, sender: bytes, txs: List[SignedTransaction]) -> None:
        # gossip batches arrive many-at-once: batch-recover senders, but
        # ONLY for txs that pass the pool's cheap dedup/gas checks first,
        # deduped within the batch itself — a batch repeating one tx (or a
        # re-gossiped batch) must cost hash lookups, not ECDSA recoveries
        with tracing.account("pool_admit"):
            seen = set()
            fresh = []
            for stx in txs:
                h = stx.hash()
                if h not in seen and self.pool.precheck(stx):
                    seen.add(h)
                    fresh.append(stx)
            warm_sender_caches(fresh, self.chain_id)
            for stx in fresh:
                self.pool.add(stx)

    def _on_ping_request(self, sender: bytes, height: int) -> None:
        self.network.send_to(
            sender, wire.ping_reply(self.block_manager.current_height())
        )

    def _on_message_request(self, sender_pub: bytes, era: int) -> None:
        """A peer is missing consensus traffic for `era`: replay our outbox
        to it (reference message-request/resend layer). Served only for eras
        the router still retains — older eras are settled on-chain and the
        requester's recovery path is block sync, which its next height probe
        triggers anyway."""
        import time as _time

        from ..utils import metrics

        if self.router is None:
            return
        sender = self._index_by_pub.get(sender_pub)
        if sender is None or sender == self.index:
            return
        now = _time.monotonic()
        key = (sender_pub, era)
        last = self._replay_served_at.get(key)
        if last is not None and now - last < self.replay_min_interval:
            metrics.inc("consensus_replay_rate_limited_total")
            return
        self._replay_served_at[key] = now
        if len(self._replay_served_at) > 4096:  # spam/memory bound
            self._replay_served_at = {
                k: v
                for k, v in self._replay_served_at.items()
                if now - v < self.replay_min_interval
            }
        # batch cap scales with fleet RTT: a distant requester's next
        # re-request is an RTT away, so each replay round carries more
        # (scale(1.0) is the dimensionless stretch factor: 1x on fast
        # links, up to 4x on slow ones)
        limit = int(self.replay_batch_limit * self.network.rtt.scale(1.0))
        payloads = self.router.outbox_payloads(era, sender)[:limit]
        for payload in payloads:
            self.network.send_to(sender_pub, wire.consensus_msg(era, payload))
        if payloads:
            metrics.inc("consensus_outbox_replayed_total", len(payloads))
            logger.info(
                "replayed %d era-%d messages to %s",
                len(payloads),
                era,
                sender_pub.hex()[:16],
            )

    # -- consensus plumbing -------------------------------------------------

    def _transport_send(self, target: Optional[int], payload) -> None:
        """EraRouter outbound: serialize + enqueue on peer workers; self
        delivery is deferred onto the event loop to keep dispatch
        non-reentrant (the reference's per-protocol queues give the same
        guarantee)."""
        assert self.router is not None
        msg = wire.consensus_msg(self.router.era, payload)
        loop = asyncio.get_running_loop()
        if target is None:
            self.network.broadcast(msg)
            loop.call_soon(self._dispatch_local, self.router.era, payload)
        elif target == self.index:
            loop.call_soon(self._dispatch_local, self.router.era, payload)
        else:
            pub = self._pub_by_index.get(target)
            if pub is not None:
                self.network.send_to(pub, msg)

    def _dispatch_local(self, era: int, payload) -> None:
        if self.router is None or self._stopping:
            return
        self.router.dispatch_external(self.index, payload)
        self._check_era_done()

    def _on_consensus(self, sender_pub: bytes, era: int, payload) -> None:
        # messages for eras ahead of the local router are stashed at the
        # NODE level keyed by transport pubkey: the router's own postponed
        # buffer holds sender INDICES, which become meaningless (and are
        # discarded) when a rotation swaps the validator set mid-boundary.
        # HBBFT has no retransmission, so dropping them could cost quorum.
        if self.router is None or era > self.router.era:
            self._stash_future(sender_pub, era, payload)
            return
        sender = self._index_by_pub.get(sender_pub)
        if sender is None:
            logger.warning("consensus message from non-validator dropped")
            return
        self.router.dispatch_external(sender, payload)
        self._check_era_done()

    _FUTURE_STASH_CAP = 512  # per sender pubkey, across eras
    _FUTURE_STASH_SENDERS = 64  # distinct pubkeys (spam/memory bound)
    _FUTURE_STASH_HORIZON = 16  # eras ahead worth keeping

    def _stash_future(self, sender_pub: bytes, era: int, payload) -> None:
        cur = self.router.era if self.router is not None else 0
        if era > cur + self._FUTURE_STASH_HORIZON:
            return  # absurdly far ahead: spam
        q = self._future_msgs.get(sender_pub)
        if q is None:
            if len(self._future_msgs) >= self._FUTURE_STASH_SENDERS:
                return  # bound the number of distinct (possibly fake) peers
            q = self._future_msgs.setdefault(sender_pub, [])
        if len(q) >= self._FUTURE_STASH_CAP:
            return
        q.append((era, payload))

    def _replay_future(self) -> None:
        """After the router advances/rebuilds, feed it any stashed messages
        for its era, re-attributed under the CURRENT index table; prune
        everything at or below the current era so entries from senders that
        never become validators cannot accumulate."""
        assert self.router is not None
        era = self.router.era
        for pub, q in list(self._future_msgs.items()):
            keep = []
            sender = self._index_by_pub.get(pub)
            for msg_era, payload in q:
                if msg_era < era:
                    continue  # stale
                if msg_era == era:
                    if sender is not None:
                        self.router.dispatch_external(sender, payload)
                    continue  # current-era traffic never outlives this call
                keep.append((msg_era, payload))
            if keep:
                self._future_msgs[pub] = keep
            else:
                self._future_msgs.pop(pub, None)
        self._check_era_done()

    def _check_era_done(self) -> None:
        if self.router is None:
            return
        pid = M.RootProtocolId(era=self.router.era)
        if self.router.result_of(pid) is not None:
            self._era_done.set()

    def _root_factory(self, pid, router) -> RootProtocol:
        return RootProtocol(
            pid,
            router,
            producer=self.producer,
            ecdsa_priv=self.private_keys.ecdsa_priv,
            ecdsa_pubs=self.public_keys.ecdsa_pub_keys,
        )

    # -- era loop (ConsensusManager.Run) ------------------------------------

    def _effective_pipeline_window(self) -> int:
        """The router's acceptance/retention window, widened by one era
        once the slowest peer's RTT crosses 150 ms: on a WAN fleet a fast
        region legitimately runs an era ahead while its traffic is still
        in flight toward us, and a loopback-sized window would drop (or
        stall on) that lead. Widening acceptance is safe — commits stay
        strictly sequential — it only stops distance being mistaken for
        misbehavior."""
        window = self.pipeline_window
        if self.network.rtt.max_srtt() > 0.15:
            window = max(window, 1)
        return window

    def _ensure_router(self, era: int) -> EraRouter:
        window = self._effective_pipeline_window()
        if self.router is None:
            self.router = EraRouter(
                era,
                self.index,
                self.public_keys,
                self.private_keys,
                self._transport_send,
                extra_factories={M.RootProtocolId: self._root_factory},
                journal=self.journal,
                evidence=self.evidence,
            )
            self.router.pipeline_window = window
        else:
            self.router.pipeline_window = window
            self.router.advance_era(era)
        self._replay_future()
        return self.router

    async def run_era(
        self, era: int, timeout: Optional[float] = 120.0
    ) -> Block:
        """Run one era to completion; returns the produced block.

        A synced block at this height supersedes the local consensus run
        (reference ConsensusManager.cs:339-349): the wait also wakes on
        block persistence so a lagging validator cannot wedge on an era the
        network already finished. With a timeout, TimeoutError is raised if
        neither consensus nor sync makes progress in `timeout` seconds
        total; timeout=None (the autonomous loop) waits indefinitely —
        sync supersession is the recovery path there.
        """
        router = self._ensure_router(era)
        self._era_done.clear()
        pid = M.RootProtocolId(era=era)
        sid = tracing.begin("era", era=era)
        # the loop thread's ledger over this era: the span ends with it
        ledger = tracing.ledger_begin()
        outcome = "aborted"
        try:
            router.internal_request(
                M.Request(from_id=None, to_id=pid, input=None)
            )
            self._check_era_done()
            loop = asyncio.get_running_loop()
            deadline = None if timeout is None else loop.time() + timeout
            while router.result_of(pid) is None:
                if self._stopping:
                    raise asyncio.CancelledError(
                        f"node stopped during era {era}"
                    )
                if self.block_manager.current_height() >= era:
                    block = self.block_manager.block_by_height(era)
                    assert block is not None
                    outcome = "synced"
                    return block
                remaining = None
                if deadline is not None:
                    remaining = deadline - loop.time()
                    if remaining <= 0:
                        outcome = "timeout"
                        raise TimeoutError(f"era {era} stalled")
                self._era_done.clear()
                self._height_event.clear()
                done = asyncio.ensure_future(self._era_done.wait())
                height = asyncio.ensure_future(self._height_event.wait())
                try:
                    # the era parks here while reader tasks feed the
                    # router: what the loop's thread then spends in
                    # select(), nothing ready, is the era's network idle
                    with tracing.loop_idle("era.net_idle", cat="net", era=era):
                        await asyncio.wait(
                            [done, height],
                            timeout=remaining,
                            return_when=asyncio.FIRST_COMPLETED,
                        )
                finally:
                    for fut in (done, height):
                        fut.cancel()
            block = router.result_of(pid)
            outcome = "consensus"
            return block
        finally:
            # cross-node causality: our era span carries OUR deterministic
            # trace id (what peers saw on our wire trailers) plus every
            # peer id observed inbound this era — the fleet merger joins
            # spans across pid lanes on exactly these ids
            tracing.end(
                sid,
                outcome=outcome,
                trace=wire.era_trace_id(self.network.public_key, era).hex(),
                peer_traces=",".join(self.network.trace_ids_for(era)),
                # WAN context on the era span: the fleet merger's
                # era-latency-vs-RTT curve reads these two together
                rtt_max_ms=round(self.network.rtt.max_srtt() * 1000.0, 1),
                # what this thread did with the era, by part and by
                # consensus family: together they sum to the span
                **tracing.ledger_end(ledger),
            )
            if self.recovery is not None:
                # a restarted node is back when it finishes an era as a
                # member, not when a synced block supersedes one
                self.recovery.era_finished(era, outcome)

    async def run_eras(self, first: int, count: int) -> List[Block]:
        return [await self.run_era(first + i) for i in range(count)]

    # -- autonomous lifecycle (reference ConsensusManager.Run, 191-360) ------

    def _send_system_tx(self, to: bytes, invocation: bytes) -> None:
        """KeyGenManager/ValidatorStatusManager outbound: build, sign, pool
        and gossip a governance/staking transaction from the node's key."""
        # system-contract calls bill the flat base fee only, so a modest
        # limit keeps the up-front balance requirement tiny (a validator
        # with most of its balance staked must still be able to emit
        # lifecycle transactions)
        tx = Transaction(
            to=to,
            value=0,
            nonce=self.pool.next_nonce(self.address20),
            gas_price=1,
            gas_limit=100_000,
            invocation=invocation,
        )
        stx = sign_transaction(tx, self.private_keys.ecdsa_priv, self.chain_id)
        # nobody is answered here: the node's own lifecycle is the caller,
        # and the transaction reaches others only through frames
        self.submit_tx(stx)

    def _install_rotated_keys(self, first_era, keyring, participants) -> None:
        """DKG finished: stash this node's new shares in the era-keyed
        wallet (reference GovernanceContract.ChangeValidators ->
        PrivateWallet.AddThresholdSignatureKeyAfterBlock)."""
        self.wallet.add_threshold_keys(
            first_era, keyring.tpke_priv, keyring.ts_share
        )
        logger.info(
            "node %d: rotated threshold keys installed from era %d",
            self.index,
            first_era,
        )

    def _on_block_persisted(self, block: Block) -> None:
        tracing.instant(
            "block_persisted", cat="block", height=block.header.index
        )
        # a persisted block is the strongest health signal: refresh the
        # tip-age clocks and forgive past watchdog strikes
        self._last_commit_mono = time.monotonic()
        self._last_commit_wall = time.time()
        self._stall_stage = 0
        snap = self.state.new_snapshot()
        self.validator_status.on_block_persisted(block, snap)
        self.keygen_manager.on_block_persisted(block, snap)
        self._record_attendance(block)
        self._height_event.set()

    def _record_attendance(self, block: Block) -> None:
        """Count each multisig signer's co-signature for the block's cycle
        and persist (reference: ValidatorAttendance.IncrementAttendance via
        RootProtocol.cs:302-303, durable in the attendance repository)."""
        from . import system_contracts as _sc

        keys = self.validator_manager.keys_for_era(block.header.index)
        if keys is None:
            return
        cycle = block.header.index // _sc.CYCLE_DURATION
        if cycle > self.attendance.next_cycle:
            from ..consensus.attendance import ValidatorAttendance

            self.attendance = ValidatorAttendance.from_bytes(
                self.attendance.to_bytes(), cycle, current_as_next=False
            )
        for idx, _sig in block.multisig.signatures:
            if 0 <= idx < len(keys.ecdsa_pub_keys):
                self.attendance.increment(keys.ecdsa_pub_keys[idx], cycle)
        self.kv.put(
            prefixed(EntryPrefix.VALIDATOR_ATTENDANCE),
            self.attendance.to_bytes(),
        )

    async def _wait_height(self, height: int) -> None:
        while (
            not self._stopping
            and self.block_manager.current_height() < height
        ):
            self._height_event.clear()
            try:
                await asyncio.wait_for(self._height_event.wait(), timeout=1.0)
            except asyncio.TimeoutError:
                pass

    def _rekey_for_era(self, era: int) -> Optional[int]:
        """Reconfigure consensus identity for `era` from the era-1 snapshot
        (ValidatorManager) and the wallet's era-keyed shares. Returns this
        node's validator index, or None when it sits this era out."""
        keys = self.validator_manager.keys_for_era(era)
        if keys is not self.public_keys:
            # ValidatorManager returns one stable object per distinct set,
            # so identity comparison is exact change detection
            self.public_keys = keys
            self._pub_by_index = {
                i: pk for i, pk in enumerate(keys.ecdsa_pub_keys)
            }
            self._index_by_pub = {
                pk: i for i, pk in self._pub_by_index.items()
            }
            self.producer.n = keys.n
        try:
            my_index = keys.ecdsa_pub_keys.index(self.ecdsa_pub)
        except ValueError:
            # demoted to observer: drop the stale-era router and identity so
            # inbound messages from the NEW set are never attributed into an
            # OLD-set router (index tables were just rebuilt above)
            self.router = None
            self.index = -1
            return None
        priv = self._private_keys_matching(keys, my_index, era)
        if priv is None:
            logger.warning(
                "node %d: in validator set for era %d but holds no matching "
                "threshold keys — observing",
                self.index,
                era,
            )
            self.router = None
            self.index = -1
            return None
        self.private_keys = priv
        self.index = my_index
        return my_index

    def _private_keys_matching(
        self, keys: PublicConsensusKeys, my_index: int, era: int
    ) -> Optional[PrivateConsensusKeys]:
        """The private share set whose TPKE verification key matches slot
        `my_index` of the era's PUBLIC set. Checking the match (one scalar
        mul) instead of trusting the wallet's era arithmetic protects
        against a rotation whose on-chain flip slipped a cycle: wallet keys
        installed for era E must not be used while an older set still
        governs (reference rescans keys at era start,
        ConsensusManager.cs:250-266)."""
        from ..crypto import bls12381 as bls

        want_vk = keys.tpke_verification_keys[my_index].y_i
        candidates = []
        wallet_keys = self.wallet.consensus_keys_for_era(era)
        if wallet_keys is not None:
            candidates.append(wallet_keys)
        candidates.append(self._genesis_private)
        for cand in candidates:
            if cand.tpke_priv is None or cand.tpke_priv.my_id != my_index:
                continue
            y = bls.g1_mul(bls.G1_GEN, cand.tpke_priv.x_i)
            if bls.g1_to_affine(y) == bls.g1_to_affine(want_vk):
                return cand
        return None

    async def run(self, first_era: int = 1, stop_at: Optional[int] = None) -> None:
        """The autonomous era loop (reference ConsensusManager.Run,
        ConsensusManager.cs:191-360): wait for block era-1, load the era's
        validator set from the era-1 snapshot and the era's keys from the
        wallet, run consensus if a member (sync supersedes a stalled era),
        fire persistence hooks, GC, advance."""
        loop = asyncio.get_running_loop()
        era = first_era
        while not self._stopping and (stop_at is None or era <= stop_at):
            era_start = loop.time()
            await self._wait_height(era - 1)
            if self._stopping:
                return
            my_index = self._rekey_for_era(era)
            if my_index is None:
                await self._wait_height(era)  # observer for this era
            else:
                self._rebuild_router(era)
                await self.run_era(era, timeout=None)
            self._finish_era_metrics(era, loop.time() - era_start)
            if self.block_interval > 0:
                remaining = self.block_interval - (loop.time() - era_start)
                if remaining > 0 and not self._stopping:
                    await asyncio.sleep(remaining)
            era += 1

    def _finish_era_metrics(
        self, era: int, wall_seconds: Optional[float] = None
    ) -> None:
        """Per-era crypto counter dump + reset (reference FinishEra ->
        DefaultCrypto.ResetBenchmark, ConsensusManager.cs:178,
        DefaultCrypto.cs:47-69)."""
        from ..utils import metrics

        if wall_seconds is not None:
            metrics.observe_hist(
                "era_wall_seconds",
                wall_seconds,
                buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0),
            )
        snap = metrics.timer_snapshot(reset=True, reset_prefix="crypto_")
        crypto = {k: v for k, v in snap.items() if k.startswith("crypto_")}
        if crypto:
            logger.info("era %d crypto benchmark: %s", era, crypto)

    def _rebuild_router(self, era: int) -> None:
        """Router for `era` under the CURRENT key set. Unlike
        _ensure_router, this also swaps identity when rotation changed the
        validator set."""
        if (
            self.router is not None
            and self.router.public_keys is not self.public_keys
        ):
            self.router = None  # key set changed: a fresh router is required
        self._ensure_router(era)
