"""Network manager: peer registry, batch verification, event dispatch.

Parity with the reference's NetworkManagerBase
(/root/reference/src/Lachain.Networking/NetworkManagerBase.cs:96-196): a
worker per peer public key, inbound batches are signature-verified then
fanned out to per-kind event handlers; consensus `send_to` addresses
validators by ECDSA public key (IConsensusMessageDeliverer.SendTo,
NetworkManagerBase.cs:66-69).
"""
from __future__ import annotations

import asyncio
import logging
import zlib
from typing import Callable, Dict, List, Optional, Sequence

from ..utils import metrics, tracing
from . import wire
from .hub import Hub, PeerAddress
from .rtt import RttTracker
from .wire import MessageBatch, MessageFactory, NetworkMessage
from .worker import ClientWorker, durable_before_wire

logger = logging.getLogger(__name__)


class NetworkManager:
    def __init__(
        self,
        ecdsa_priv: bytes,
        host: str = "127.0.0.1",
        port: int = 0,
        *,
        flush_interval: float = 0.25,
        advertise_host: Optional[str] = None,
        barrier: Optional[Callable[[], None]] = None,
    ):
        # persist-before-transmit, the frame's half: run in front of every
        # write of ours to a socket (worker.durable_before_wire) — by each
        # worker before its frame, and by _send_inbound before a reverse
        # delivery. The manager is its one owner.
        self._barrier = barrier
        # the address peers should DIAL — differs from the bind host when
        # binding a wildcard (0.0.0.0) or behind NAT in multi-host deploys
        self.advertise_host = advertise_host or host
        self.factory = MessageFactory(ecdsa_priv)
        self.public_key = self.factory.public_key
        self.hub = Hub(host, port, self._on_raw_batch)
        self._flush_interval = flush_interval
        self._workers: Dict[bytes, ClientWorker] = {}
        # sends addressed to peers we have not discovered yet: buffered
        # (bounded per peer) and drained the moment the address is learned —
        # consensus protocols do not retransmit, so a message dropped during
        # the bootstrap/discovery race can wedge an era (a lost RBC ECHO is
        # unrecoverable for the slot)
        self._undelivered: Dict[bytes, List[NetworkMessage]] = {}
        self._undelivered_cap = 2048
        # peers a restarted node has not heard from yet (watch_first_frames);
        # empty on every other node, so a frame pays one falsy test
        self._unseen: set = set()
        self._on_peer_seen: Optional[Callable[[int], None]] = None
        # trace-context trailers observed on verified inbound batches:
        # era -> {trace id hex}. Bounded to the newest _TRACE_ERA_KEEP
        # eras — the fleet merger only correlates recent eras, and a
        # byzantine peer stamping absurd era numbers can at worst cycle
        # this dict, never grow it (ids per era are bounded by peers)
        self.era_trace_ids: Dict[int, set] = {}
        self._TRACE_ERA_KEEP = 8
        # event handlers: fn(sender_pubkey, message)
        self.on_consensus: Optional[Callable[[bytes, int, object], None]] = None
        self.on_ping_request: Optional[Callable[[bytes, int], None]] = None
        self.on_ping_reply: Optional[Callable[[bytes, int], None]] = None
        self.on_sync_blocks_request: Optional[Callable] = None
        self.on_fast_sync_request: Optional[Callable] = None
        self.on_fast_sync_reply: Optional[Callable] = None
        self.on_trie_nodes_request: Optional[Callable] = None
        self.on_trie_nodes_reply: Optional[Callable] = None
        # request-id variants (fn(sender, request_id, ...)) + cursor-paged
        # snapshot shipping — the multi-peer fast-sync exchange
        self.on_trie_nodes_request_id: Optional[Callable] = None
        self.on_trie_nodes_reply_id: Optional[Callable] = None
        self.on_snapshot_request: Optional[Callable] = None
        self.on_snapshot_reply: Optional[Callable] = None
        self.on_sync_blocks_reply: Optional[Callable] = None
        self.on_sync_pool_request: Optional[Callable] = None
        self.on_sync_pool_reply: Optional[Callable] = None
        # consensus retransmission: fn(sender_pubkey, era) — the node
        # answers by replaying its era outbox to the sender
        self.on_message_request: Optional[Callable[[bytes, int], None]] = None
        # gossip peer discovery: fired when a previously-unknown peer is
        # learned from a peers_reply (after the worker already exists)
        self.on_peer_discovered: Optional[Callable[[PeerAddress], None]] = None
        # --- relay / NAT traversal (reference Hub/HubConnector.cs) ---
        # as a RELAY: registered NAT'd clients + the inbound connection
        # each last spoke on (reverse-delivery path)
        self.relay_clients: Dict[bytes, float] = {}   # pub -> last seen
        self._last_conn: Dict[bytes, int] = {}        # pub -> conn id
        self._relay_client_ttl = 90.0
        # as a NAT'D NODE: the relay we registered with (None = direct),
        # plus the configured fallback list for relay HA: when the current
        # relay stops answering, registration fails over down the list
        self._my_relay: Optional[PeerAddress] = None
        self._relays: List[PeerAddress] = []
        self._relay_idx = 0
        self.relay_failover_after = 3  # consecutive send failures
        self._reregister_task = None
        # as a SENDER: peers reachable only through a relay
        self._relay_route: Dict[bytes, bytes] = {}    # peer pub -> relay pub
        # --- WAN adaptivity ---
        # per-peer RTT EWMAs off the ping exchange; timeout scaling for the
        # watchdog / synchronizer / reconnect rationing reads these
        self.rtt = RttTracker()
        # wire/engine versions peers have advertised via the LTRX batch
        # tail. Absent entry = legacy peer (assumed wire v1); gating only
        # ever applies to EXPLICITLY-advertised-older peers, so a fleet of
        # pre-handshake builds behaves exactly as before
        self.peer_versions: Dict[bytes, wire.WireHandshake] = {}
        # strike-3 forced-reconnect rationing: a per-peer token bucket so
        # sustained high RTT cannot reconnect-thrash a slow-but-alive peer
        # every escalation cycle. Refill interval stretches with observed
        # fleet RTT (slower fleet -> scarcer reconnects).
        self.reconnect_bucket_capacity = 2.0
        self.reconnect_min_interval = 30.0
        self._reconnect_buckets: Dict[bytes, List[float]] = {}

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        await self.hub.start()

    async def stop(self) -> None:
        if self._reregister_task is not None:
            self._reregister_task.cancel()
            self._reregister_task = None
        # a snapshot: the hub still delivers while a worker stops, and a
        # peer's signed peers_request may rebind (pop and insert) meanwhile
        for w in list(self._workers.values()):
            await w.stop()
        await self.hub.stop()

    # -- relay / NAT traversal ---------------------------------------------

    def use_relay(self, relay, reregister_every: float = 20.0) -> None:
        """NAT'd mode: register with a relay and advertise ourselves as
        reachable through it. The registration re-sends periodically —
        it refreshes the relay's TTL and keeps the NAT mapping warm.

        `relay` is one PeerAddress or a LIST of them (relay HA): the node
        registers with the first and, when that relay's worker accumulates
        `relay_failover_after` consecutive send failures, rotates to the
        next one and re-advertises the new route to every peer (the
        self-declared address in a peers_request is authoritative, so the
        rebind propagates without any relay cooperation)."""
        self._relays = (
            list(relay) if isinstance(relay, (list, tuple)) else [relay]
        )
        if not self._relays:
            raise ValueError("use_relay: empty relay list")
        self._relay_idx = 0
        self._register_with(self._relays[0])

        async def rereg():
            while True:
                await asyncio.sleep(reregister_every)
                self._maybe_failover_relay()
                assert self._my_relay is not None
                self.send_to(self._my_relay.public_key, wire.relay_register())

        try:
            self._reregister_task = asyncio.get_running_loop().create_task(
                rereg()
            )
        except RuntimeError:
            # no loop (offline construction): without periodic
            # re-registration the relay's TTL expires in 90s and reverse
            # delivery silently stops — surface it instead of skipping
            logger.warning(
                "use_relay without a running event loop: relay "
                "re-registration NOT scheduled; caller must re-register"
            )
            metrics.inc("network_relay_reregister_skipped_total")

    def _register_with(self, relay: PeerAddress) -> None:
        self._my_relay = relay
        self.add_peer(relay, authoritative=True)
        self.send_to(relay.public_key, wire.relay_register())

    def _maybe_failover_relay(self) -> None:
        """Rotate to the next configured relay when the current one has
        stopped accepting our traffic. The signal is the relay WORKER's
        consecutive-failure counter — the same health signal that drives
        its backoff — so a relay that merely drops reverse traffic but
        still ACKs ours is out of scope (peers' message_request recovery
        covers that loss)."""
        if len(self._relays) < 2 or self._my_relay is None:
            return
        worker = self._workers.get(self._my_relay.public_key)
        if (
            worker is None
            or worker.consecutive_failures < self.relay_failover_after
        ):
            return
        self._relay_idx = (self._relay_idx + 1) % len(self._relays)
        new = self._relays[self._relay_idx]
        logger.warning(
            "relay %s unresponsive (%d consecutive failures): failing over "
            "to %s:%d",
            self._my_relay.public_key.hex()[:16],
            worker.consecutive_failures,
            new.host,
            new.port,
        )
        metrics.inc("network_relay_failovers_total")
        self._register_with(new)
        # our advertised address just changed (the relay sentinel embeds
        # the relay's pubkey): push the rebind to every peer now — the
        # self-declared address in a peers_request is authoritative
        adv_host, adv_port = self.advertised_host_port
        for pub, w in self._workers.items():
            if pub != new.public_key:
                w.enqueue(wire.peers_request(adv_host, adv_port))

    @property
    def advertised_host_port(self):
        """What we tell peers to reach us at: the relay sentinel when
        NAT'd, the real listening address otherwise."""
        if self._my_relay is not None:
            return wire.relay_host(self._my_relay.public_key), 0
        return self.advertise_host, self.hub.port

    def _relay_transport(self, target_pub: bytes, relay_pub: bytes):
        """ClientWorker transport for a relay-routed peer: wrap each signed
        batch in a relay_forward and queue it to the RELAY's worker."""

        async def send(_peer, batch_bytes: bytes) -> bool:
            relay_worker = self._workers.get(relay_pub)
            if relay_worker is None:
                return False
            relay_worker.enqueue(
                wire.relay_forward(target_pub, batch_bytes)
            )
            return True

        return send

    @property
    def address(self) -> PeerAddress:
        return PeerAddress(self.public_key, self.hub.host, self.hub.port)

    def add_peer(self, peer: PeerAddress, authoritative: bool = True) -> None:
        """Install (or update) the dialing address for a peer.

        `authoritative` addresses come from config or from the peer ITSELF
        (a peers_request rides a signature-verified batch from that pubkey)
        and may REPLACE an existing binding — a restarted peer on a new
        port, or a binding poisoned by bogus gossip, corrects itself the
        moment the real peer makes contact. Third-party gossip
        (peers_reply entries) is non-authoritative: it can only introduce
        UNKNOWN peers, never rebind a known one, so a Byzantine address
        book cannot blackhole traffic to a validator we already reach.

        A host of the form "~<relay pub hex>" (wire.relay_host) marks a
        peer reachable only THROUGH that relay: its worker sends
        relay_forward envelopes to the relay instead of dialing.
        """
        if peer.public_key == self.public_key:
            return
        relay_pub = wire.parse_relay_host(peer.host)
        if relay_pub is not None:
            if relay_pub == self.public_key:
                # we ARE this peer's relay: it reaches us inbound; traffic
                # back to it rides its own connection (send_to fallback).
                # It must be a registered client to be deliverable at all.
                for msg in self._undelivered.pop(peer.public_key, ()):
                    self.send_to(peer.public_key, msg)
                return
            if relay_pub not in self._workers:
                logger.info(
                    "peer %s advertises unknown relay %s; dropped",
                    peer.public_key.hex()[:16], relay_pub.hex()[:16],
                )
                return
            old_route = self._relay_route.get(peer.public_key)
            if old_route == relay_pub and peer.public_key in self._workers:
                return
            if not authoritative and peer.public_key in self._workers:
                # third-party gossip may only INTRODUCE unknown peers — it
                # can neither demote a direct binding NOR move an existing
                # relay route to a different relay (a Byzantine address
                # book would blackhole the victim's traffic at a relay
                # holding no registration for it)
                return
            self._relay_route[peer.public_key] = relay_pub
            old = self._workers.pop(peer.public_key, None)
            if old is not None:
                try:
                    asyncio.get_running_loop().create_task(old.stop())
                except RuntimeError:
                    # no running loop (offline construction/tests): the
                    # worker's tasks were never started, nothing to stop
                    logger.debug(
                        "no running loop; old relay worker for %s dropped "
                        "without async stop",
                        peer.public_key.hex()[:16],
                    )
            worker = ClientWorker(
                peer, self.factory, self.hub,
                flush_interval=self._flush_interval,
                transport=self._relay_transport(peer.public_key, relay_pub),
                barrier=self._barrier,
            )
            self._workers[peer.public_key] = worker
            worker.start()
            host, port = self.advertised_host_port
            worker.enqueue(wire.peers_request(host, port))
            for msg in self._undelivered.pop(peer.public_key, ()):
                worker.enqueue(msg)
            return
        old = self._workers.get(peer.public_key)
        if old is not None:
            if not authoritative or (
                old.peer.host == peer.host and old.peer.port == peer.port
            ):
                # REJECTED updates must not touch state: popping the relay
                # route before this check let refused Byzantine gossip
                # erase a relay-routed peer's entry (its next re-advert
                # then tore down and recreated the worker, dropping its
                # queued consensus messages)
                return
        # accepted direct binding: it supersedes any relay route
        self._relay_route.pop(peer.public_key, None)
        if old is not None:
            # self-declared address change: rebind
            logger.info(
                "peer %s rebinds %s:%d -> %s:%d",
                peer.public_key.hex()[:16],
                old.peer.host, old.peer.port, peer.host, peer.port,
            )
            self._workers.pop(peer.public_key, None)
            try:
                asyncio.get_running_loop().create_task(old.stop())
            except RuntimeError:  # no running loop (tests)
                logger.debug(
                    "no running loop; rebound worker for %s dropped "
                    "without async stop",
                    peer.public_key.hex()[:16],
                )
        worker = ClientWorker(
            peer, self.factory, self.hub,
            flush_interval=self._flush_interval,
            barrier=self._barrier,
        )
        self._workers[peer.public_key] = worker
        worker.start()
        # gossip crawl: ask every new acquaintance for its address book,
        # carrying our own dialable address so it can dial back
        # (config-seeded + gossip-learned peers; reference reaches peers
        # through bootstrap relays, HubConnector.cs:26-105 +
        # config_mainnet.json:22-33)
        adv_host, adv_port = self.advertised_host_port
        worker.enqueue(wire.peers_request(adv_host, adv_port))
        for msg in self._undelivered.pop(peer.public_key, ()):
            worker.enqueue(msg)

    @property
    def peers(self) -> List[bytes]:
        return list(self._workers.keys())

    def watch_first_frames(
        self, public_keys: List[bytes], on_seen: Callable[[int], None]
    ) -> None:
        """Call `on_seen(k)` at the first verified frame from each of
        `public_keys`, k = how many of them are still silent then. What a
        restarted node times its reconnection with (core/recovery.py): a
        frame from a peer means that peer's worker has dialed it again."""
        self._unseen = set(public_keys)
        self._on_peer_seen = on_seen

    # -- sending -----------------------------------------------------------

    def wire_version_of(self, public_key: bytes) -> Optional[int]:
        """The wire version `public_key` has advertised, None when it never
        has (legacy peer or no traffic yet)."""
        hs = self.peer_versions.get(public_key)
        return hs.wire_version if hs is not None else None

    def _version_gated(self, public_key: bytes, msg: NetworkMessage) -> bool:
        """True when `msg` must NOT be sent to `public_key`: the peer has
        EXPLICITLY advertised a wire version too old to decode the kind
        (its decoder would reject the whole batch, dropping innocent
        messages sharing the flush). Unknown peers are never gated —
        pre-handshake fleets keep the status quo."""
        advertised = self.wire_version_of(public_key)
        if advertised is None:
            return False
        if advertised >= wire.KIND_MIN_WIRE.get(msg.kind, 1):
            return False
        metrics.inc(
            "network_msgs_version_gated_total",
            labels={"kind": str(msg.kind)},
        )
        logger.debug(
            "kind=%d gated toward peer %s (advertised wire v%d)",
            msg.kind, public_key.hex()[:16], advertised,
        )
        return True

    def send_to(self, public_key: bytes, msg: NetworkMessage) -> None:
        if self._version_gated(public_key, msg):
            return
        if msg.kind == wire.KIND_PING_REQUEST:
            self.rtt.note_sent(public_key)
        worker = self._workers.get(public_key)
        if worker is None:
            self._prune_relay_clients()
            if public_key in self.relay_clients:
                # OUR registered NAT'd client: answer over its own inbound
                # connection (the only path that reaches it)
                batch = self.factory.batch([msg])
                self._send_inbound(public_key, batch.encode(), msg)
                return
            self._buffer_undelivered(public_key, msg)
            return
        worker.enqueue(msg)

    def _buffer_undelivered(self, public_key: bytes, msg) -> None:
        pending = self._undelivered.setdefault(public_key, [])
        if len(pending) < self._undelivered_cap:
            pending.append(msg)
        else:
            # a silently-vanished consensus message here is exactly the
            # wedged-era failure mode: make the loss observable so the
            # metric can alarm and the log names the victim
            logger.warning(
                "undelivered buffer full for peer %s: dropping kind=%d",
                public_key.hex()[:16],
                msg.kind,
            )
            metrics.inc(
                "network_undelivered_dropped_total",
                labels={"kind": str(msg.kind)},
            )

    def _send_inbound(
        self, public_key: bytes, data: bytes, msg=None
    ) -> None:
        """Reverse-deliver to a relay client. `msg` (when given) is
        re-buffered on failure — consensus protocols do not retransmit,
        so a message lost while the client re-dials would wedge an era
        (same rationale as the _undelivered buffer for direct peers).
        The buffer drains when the client's next batch arrives
        (_on_raw_batch refreshes _last_conn and drains)."""
        conn_id = self._last_conn.get(public_key)
        if conn_id is None:
            if msg is not None:
                self._buffer_undelivered(public_key, msg)
            return

        async def deliver():
            # no worker stands between send_to and this socket, so the
            # node's barrier (journal and pool) is taken here
            ok = durable_before_wire(self._barrier) and (
                await self.hub.send_on_conn(conn_id, data)
            )
            if not ok and msg is not None:
                self._buffer_undelivered(public_key, msg)

        try:
            asyncio.get_running_loop().create_task(deliver())
        except RuntimeError:
            # no running loop: reverse delivery needs the hub's socket,
            # so the message can only wait for the client's next contact
            logger.debug(
                "no running loop; reverse delivery to %s buffered",
                public_key.hex()[:16],
            )
            if msg is not None:
                self._buffer_undelivered(public_key, msg)

    def _prune_relay_clients(self) -> None:
        import time

        now = time.monotonic()
        expired = [
            p for p, t in self.relay_clients.items()
            if now - t > self._relay_client_ttl
        ]
        for p in expired:
            del self.relay_clients[p]
            self._last_conn.pop(p, None)

    def broadcast(self, msg: NetworkMessage) -> None:
        for pub, worker in self._workers.items():
            if self._version_gated(pub, msg):
                continue
            if msg.kind == wire.KIND_PING_REQUEST:
                self.rtt.note_sent(pub)
            worker.enqueue(msg)

    # -- failure handling ----------------------------------------------------

    def install_faults(self, plan, my_id: int, salt: Optional[int] = None):
        """Wire a FaultPlan into this node's TCP path: frames to peers run
        the plan's link decisions (dst resolved by worker pubkey -> the
        index the caller registers via `map_fault_peer`). Returns the
        TcpFrameFilter so tests/CLI can read its stats."""
        from .faults import TcpFrameFilter

        session = plan.session(salt=my_id if salt is None else salt)
        self._fault_peer_ids: Dict[bytes, int] = {}

        def peer_index(peer) -> Optional[int]:
            if peer is None:
                return None
            return self._fault_peer_ids.get(peer.public_key)

        filt = TcpFrameFilter(session, my_id, peer_index)
        self.hub.frame_filter = filt
        return filt

    def map_fault_peer(self, public_key: bytes, node_id: int) -> None:
        """Tell the installed fault filter which plan node a transport
        identity is (link-level partitions/crashes need the mapping)."""
        getattr(self, "_fault_peer_ids", {})[public_key] = node_id

    def install_wan_shaper(
        self, spec, my_id: int, validator_pubs: Sequence[bytes], seed: int
    ):
        """The one way a validator gets its emulated WAN (config
        network.wanShaper, the fleet harness, the benchmark's hb16-wan):
        `spec` is a LinkShaper or its spec string, `my_id` this validator's
        index, `validator_pubs` the committee's ECDSA keys in index order —
        validator indices are the shaper's node ids, the striping `keygen
        --regions` writes into network.region. Every node of a fleet
        carries the same spec and seed, so the pairwise matrix is
        consistent although each node only shapes its own sends. Returns
        the TcpFrameFilter (its session holds the stats)."""
        from .faults import FaultPlan, LinkShaper

        shaper = LinkShaper.parse(spec) if isinstance(spec, str) else spec
        filt = self.install_faults(FaultPlan(seed=seed, shaper=shaper), my_id)
        for j, pub in enumerate(validator_pubs):
            self.map_fault_peer(pub, j)
        return filt

    def _reconnect_allowed(self, public_key: bytes, now: float) -> bool:
        """Spend one token from `public_key`'s reconnect bucket. Refill is
        one token per reconnect_min_interval, with the interval stretched
        by the fleet RTT estimate: on a 200 ms-RTT fleet a strike-3 cycle
        fires on a loopback-tuned schedule, and uncapped it would tear
        down and re-dial a slow-but-alive peer's connection faster than
        the handshake + zlib warmup it just threw away."""
        interval = self.rtt.scale(self.reconnect_min_interval)
        bucket = self._reconnect_buckets.get(public_key)
        if bucket is None:
            bucket = self._reconnect_buckets[public_key] = [
                self.reconnect_bucket_capacity, now
            ]
        tokens, last = bucket
        tokens = min(
            self.reconnect_bucket_capacity,
            tokens + (now - last) / interval,
        )
        if tokens < 1.0:
            bucket[0], bucket[1] = tokens, now
            return False
        bucket[0], bucket[1] = tokens - 1.0, now
        return True

    def reconnect_peers(self, *, force: bool = False) -> int:
        """Stall-escalation last resort: drop cached outbound sockets and
        reset worker backoff, so the next flush re-dials immediately
        instead of waiting out an exponential-backoff window against a
        peer that already recovered. Rationed per peer through an
        RTT-scaled token bucket (`force=True` bypasses — operator CLI);
        returns the number of peers actually reconnected."""
        import time

        now = time.monotonic()
        reconnected = 0
        for pub, worker in self._workers.items():
            if not force and not self._reconnect_allowed(pub, now):
                metrics.inc("watchdog_reconnects_suppressed_total")
                logger.info(
                    "reconnect of peer %s suppressed (token bucket)",
                    pub.hex()[:16],
                )
                continue
            key = (worker.peer.host, worker.peer.port)
            conn = self.hub._conns.pop(key, None)
            if conn is not None:
                conn.close()
            worker.reset_backoff()
            reconnected += 1
        if reconnected:
            metrics.inc("network_forced_reconnect_total")
            logger.warning(
                "forcing reconnect of %d peer connections", reconnected
            )
        return reconnected

    # -- receiving ---------------------------------------------------------

    def _on_raw_batch(self, data: bytes, conn_id: Optional[int] = None) -> None:
        # the loop's part `frame_in`: an inbound frame less its verification
        # and whatever its handlers claim (a consensus family, pool_admit)
        with tracing.account("frame_in"):
            self._on_frame(data, conn_id)

    def _on_frame(self, data: bytes, conn_id: Optional[int]) -> None:
        try:
            batch = MessageBatch.decode(data)
        except ValueError:
            logger.warning("undecodable batch dropped")
            return
        with tracing.account("frame_verify"):
            verified = batch.verify()
        if not verified:
            logger.warning("batch with bad signature dropped")
            return
        metrics.inc("network_frames_total", labels={"dir": "in"})
        if self._unseen and batch.sender in self._unseen:
            self._unseen.discard(batch.sender)
            self._on_peer_seen(len(self._unseen))
        try:
            msgs = batch.messages()
        except (ValueError, zlib.error):
            logger.warning("corrupt batch content dropped")
            return
        self._note_trace_ctx(batch)
        self._note_handshake(batch)
        if conn_id is not None:
            # remember the latest live inbound connection per verified
            # sender: the reverse-delivery path to NAT'd relay clients.
            # A reconnecting client also drains anything buffered while
            # its connection was down.
            self._last_conn[batch.sender] = conn_id
            if batch.sender in self.relay_clients:
                for m in self._undelivered.pop(batch.sender, ()):
                    self.send_to(batch.sender, m)
        for msg in msgs:
            try:
                self._dispatch(batch.sender, msg)
            except Exception:
                logger.exception("message handler failed")

    def _note_trace_ctx(self, batch: MessageBatch) -> None:
        """Record the sender's trace context from a VERIFIED batch: the
        receiving node's consensus spans for that era can then carry the
        peer's trace id (cross-node causality for RBC echo/ready and coin
        shares in the merged fleet trace). First sighting of an id per era
        emits a wire.trace_ctx instant; repeats are a set probe."""
        ctx = batch.trace_trailer()
        if ctx is None:
            return
        origin, era, tid = ctx
        ids = self.era_trace_ids.get(era)
        if ids is None:
            ids = self.era_trace_ids[era] = set()
            while len(self.era_trace_ids) > self._TRACE_ERA_KEEP:
                del self.era_trace_ids[min(self.era_trace_ids)]
        tid_hex = tid.hex()
        if tid_hex not in ids:
            ids.add(tid_hex)
            tracing.instant(
                "wire.trace_ctx",
                cat="net",
                era=era,
                trace=tid_hex,
                origin=origin.hex(),
                sender=batch.sender.hex()[:16],
            )

    def _note_handshake(self, batch: MessageBatch) -> None:
        """Record the sender's advertised versions from a VERIFIED batch.
        Logged on first sighting and on change (a mid-roll restart flips a
        peer's version); incompatible peers are surfaced loudly but NOT
        disconnected — the adjacency contract makes |Δ|<=1 interoperable,
        and anything wider is an operator error the metric should page on,
        not a reason to shrink quorum further."""
        hs = batch.handshake()
        if hs is None:
            return
        prev = self.peer_versions.get(batch.sender)
        if prev == hs:
            return
        self.peer_versions[batch.sender] = hs
        metrics.set_gauge(
            "network_peer_wire_version",
            hs.wire_version,
            labels={"peer": batch.sender[:4].hex()},
        )
        logger.info(
            "peer %s advertises wire v%d engine v%d features=0x%x",
            batch.sender.hex()[:16],
            hs.wire_version, hs.engine_version, hs.features,
        )
        if not wire.compatible(hs.wire_version, self.factory.wire_version):
            metrics.inc("network_peer_version_incompatible_total")
            logger.error(
                "peer %s wire v%d is OUTSIDE the v%d±1 compatibility "
                "window — upgrade lag exceeds one version",
                batch.sender.hex()[:16],
                hs.wire_version, self.factory.wire_version,
            )

    def trace_ids_for(self, era: int) -> List[str]:
        """Trace ids seen on inbound consensus traffic for `era` (sorted
        for deterministic span annotations)."""
        return sorted(self.era_trace_ids.get(era, ()))

    def _dispatch(self, sender: bytes, msg: NetworkMessage) -> None:
        k = msg.kind
        if k == wire.KIND_CONSENSUS and self.on_consensus:
            era, payload = wire.parse_consensus(msg)
            self.on_consensus(sender, era, payload)
        elif k == wire.KIND_PING_REQUEST and self.on_ping_request:
            self.on_ping_request(sender, wire.parse_height(msg))
        elif k == wire.KIND_PING_REPLY:
            # RTT sample first: the ping exchange doubles as the WAN
            # latency instrument (network/rtt.py)
            self.rtt.note_reply(sender)
            w = self._workers.get(sender)
            if w is not None:
                # redial pacing floor: retrying faster than the link's
                # RTT burns dials that cannot have completed yet
                w.backoff_floor = self.rtt.srtt(sender) or 0.0
            if self.on_ping_reply:
                self.on_ping_reply(sender, wire.parse_height(msg))
        elif k == wire.KIND_SYNC_BLOCKS_REQUEST and self.on_sync_blocks_request:
            start, count = wire.parse_sync_blocks_request(msg)
            self.on_sync_blocks_request(sender, start, count)
        elif k == wire.KIND_SYNC_BLOCKS_REPLY and self.on_sync_blocks_reply:
            self.on_sync_blocks_reply(sender, wire.parse_sync_blocks_reply(msg))
        elif k == wire.KIND_SYNC_POOL_REQUEST and self.on_sync_pool_request:
            self.on_sync_pool_request(sender, wire.parse_sync_pool_request(msg))
        elif k == wire.KIND_SYNC_POOL_REPLY and self.on_sync_pool_reply:
            self.on_sync_pool_reply(sender, wire.parse_sync_pool_reply(msg))
        elif k == wire.KIND_FAST_SYNC_REQUEST and self.on_fast_sync_request:
            self.on_fast_sync_request(sender, wire.parse_fast_sync_request(msg))
        elif k == wire.KIND_FAST_SYNC_REPLY and self.on_fast_sync_reply:
            self.on_fast_sync_reply(sender, *wire.parse_fast_sync_reply(msg))
        elif k == wire.KIND_TRIE_NODES_REQUEST and self.on_trie_nodes_request:
            self.on_trie_nodes_request(sender, wire.parse_trie_nodes_request(msg))
        elif k == wire.KIND_TRIE_NODES_REPLY and self.on_trie_nodes_reply:
            self.on_trie_nodes_reply(sender, wire.parse_trie_nodes_reply(msg))
        elif k == wire.KIND_TRIE_NODES_REQUEST_ID and self.on_trie_nodes_request_id:
            rid, hashes = wire.parse_trie_nodes_request_id(msg)
            self.on_trie_nodes_request_id(sender, rid, hashes)
        elif k == wire.KIND_TRIE_NODES_REPLY_ID and self.on_trie_nodes_reply_id:
            rid, nodes = wire.parse_trie_nodes_reply_id(msg)
            self.on_trie_nodes_reply_id(sender, rid, nodes)
        elif k == wire.KIND_SNAPSHOT_REQUEST and self.on_snapshot_request:
            rid, cursor, limit = wire.parse_snapshot_request(msg)
            self.on_snapshot_request(sender, rid, cursor, limit)
        elif k == wire.KIND_SNAPSHOT_REPLY and self.on_snapshot_reply:
            rid, next_cursor, done, records = wire.parse_snapshot_reply(msg)
            self.on_snapshot_reply(sender, rid, next_cursor, done, records)
        elif k == wire.KIND_MESSAGE_REQUEST and self.on_message_request:
            self.on_message_request(sender, wire.parse_message_request(msg))
        elif k == wire.KIND_PEERS_REQUEST:
            self._on_peers_request(sender, msg)
        elif k == wire.KIND_PEERS_REPLY:
            self._on_peers_reply(msg)
        elif k == wire.KIND_RELAY_REGISTER:
            self._on_relay_register(sender)
        elif k == wire.KIND_RELAY_FORWARD:
            self._on_relay_forward(sender, msg)

    # -- relaying ----------------------------------------------------------

    def _on_relay_register(self, sender: bytes) -> None:
        import time

        now = time.monotonic()
        fresh = sender not in self.relay_clients
        self.relay_clients[sender] = now
        self._prune_relay_clients()
        if fresh:
            logger.info(
                "relay client registered: %s", sender.hex()[:16]
            )
            # the client may have been buffered as undeliverable before
            for m in self._undelivered.pop(sender, ()):
                self.send_to(sender, m)

    def _on_relay_forward(self, sender: bytes, msg: NetworkMessage) -> None:
        try:
            target, inner = wire.parse_relay_forward(msg)
        except ValueError:
            logger.warning("malformed relay_forward dropped")
            return
        if target == self.public_key:
            # an envelope addressed to US (we are the NAT'd node and the
            # relay delivered over our outbound conn): unwrap and process
            # the inner batch — its own signature authenticates the origin
            self._on_raw_batch(inner)
            return
        self._prune_relay_clients()
        if target not in self.relay_clients:
            logger.warning(
                "relay_forward from %s for unregistered %s dropped",
                sender.hex()[:16], target.hex()[:16],
            )
            return
        self._send_inbound(target, inner)

    # -- gossip peer discovery ---------------------------------------------

    def _on_peers_request(self, sender: bytes, msg: NetworkMessage) -> None:
        host, port = wire.parse_peers_request(msg)
        # the requester's self-declared address arrived under its own batch
        # signature: authoritative (installs OR rebinds), so an inbound-only
        # acquaintance gets a worker to carry the reply
        self.add_peer(
            PeerAddress(public_key=sender, host=host, port=port),
            authoritative=True,
        )
        book = [
            (w.peer.public_key, w.peer.host, w.peer.port)
            for w in self._workers.values()
            if w.peer.public_key != sender
        ]
        # our registered NAT'd clients are reachable THROUGH us (pruned
        # first: a dead client must not be advertised into a void)
        self._prune_relay_clients()
        me = wire.relay_host(self.public_key)
        for pub in self.relay_clients:
            if pub != sender:
                book.append((pub, me, 0))
        adv_host, adv_port = self.advertised_host_port
        book.append((self.public_key, adv_host, adv_port))
        self.send_to(sender, wire.peers_reply(book))

    def _on_peers_reply(self, msg: NetworkMessage) -> None:
        try:
            entries = wire.parse_peers_reply(msg)
        except ValueError:
            logger.warning("malformed peers reply dropped")
            return
        for pub, host, port in entries:
            if pub == self.public_key or pub in self._workers:
                continue
            peer = PeerAddress(public_key=pub, host=host, port=port)
            # third-party gossip: may only INTRODUCE unknown peers
            self.add_peer(peer, authoritative=False)
            if self.on_peer_discovered:
                try:
                    self.on_peer_discovered(peer)
                except Exception:
                    logger.exception("peer-discovered handler failed")
