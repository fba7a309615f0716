"""Deterministic fault injection: one plan, every delivery layer.

A :class:`FaultPlan` is a seeded, declarative description of an adversarial
network — message loss/delay/duplication/reordering probabilities, link-level
partitions with heal times, and scheduled peer crash/restart windows. The
same plan object drives three delivery layers:

  * the in-process simulator (`consensus/simulator.py`) — virtual clock is
    the delivered-message count, recovery is modeled by outbox replay on
    quiescence;
  * the native engine (`consensus/native_rt.py`) — the plan maps onto the
    engine's own knobs (duplicate ppm, reorder mode, muted players);
  * the real TCP path (`network/hub.py`) — a :class:`TcpFrameFilter` built
    from the plan drops/delays/duplicates framed batches on the socket,
    clocked by wall time.

Every probabilistic decision draws from a `random.Random` seeded from
`(plan.seed, salt)`: a layer replaying the same decision sequence replays
the same faults, which is what makes a recorded production failure
reproducible from its seed (HoneyBadgerBFT only guarantees liveness under
eventual delivery — the recovery layer must be provoked deterministically
to be testable at all).

Time units are layer-relative: the simulator clocks in delivered messages,
the TCP filter in seconds since installation. A plan authored for one layer
therefore needs its schedule rescaled for the other; probabilities carry
over unchanged.

WAN emulation rides on the same contract: a :class:`LinkShaper` attached to
the plan gives every (region, region) link a base latency, jitter (with
seeded burst windows), and a bandwidth cap enforced by a per-link pacer.
Shaped latency is expressed through the existing `decide()` delay-list
interface, so the simulator, the TCP frame filter, and the hub's delay
timers all carry it with no extra plumbing — and the decisions draw from
the same seeded rng, so two same-seed runs shape bit-identically.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, FrozenSet, List, Mapping, Optional, Tuple

from ..utils import metrics


@dataclass(frozen=True)
class Crash:
    """Node `node` crashes at `at` and restarts at `restart` (None = never).

    A crashed node neither sends nor processes; on restart it rejoins with
    its in-memory state intact (process-level restart with state loss is the
    block-sync path, not this layer's job)."""

    node: int
    at: float
    restart: Optional[float] = None


@dataclass(frozen=True)
class Partition:
    """Link-level split: traffic between `side_a` and `side_b` is blocked
    from `at` until `heal` (None = never heals). Intra-side traffic and
    nodes on neither side are unaffected."""

    side_a: FrozenSet[int]
    side_b: FrozenSet[int]
    at: float
    heal: Optional[float] = None


@dataclass(frozen=True)
class LinkShape:
    """One directed region->region link's shape, in the layer's clock/size
    units (seconds + bytes on TCP, virtual ticks + nominal frame units in
    the simulator)."""

    latency: float = 0.0    # one-way base latency
    jitter: float = 0.0     # uniform extra delay in [0, jitter]
    bandwidth: float = 0.0  # link capacity, size units per clock unit; 0 = uncapped


@dataclass(frozen=True)
class LinkShaper:
    """Seeded WAN link shaping: a per-region-pair latency/jitter/bandwidth
    matrix applied to every frame a FaultSession decides on.

    Node -> region assignment is positional (`regions[node % len]`), so a
    16-node fleet over `("us", "eu", "ap", "sa")` stripes four emulated
    regions. Links are DIRECTED: `links[("us", "eu")]` may differ from
    `links[("eu", "ap")]` (asymmetric paths); a missing ordered pair falls
    back to the reversed pair, then to `default` for cross-region links.
    Intra-region links are unshaped unless an explicit ("r", "r") entry or
    `intra` exists. Jitter draws come from the session's seeded rng and
    occasionally land in burst windows (`jitter_burst` probability) where
    the draw is amplified `burst_multiplier`x — the WAN microburst model.
    The bandwidth cap is a per-link serialization pacer: frame `k` cannot
    start before frame `k-1` finished transmitting at `bandwidth`
    units/clock-unit, so a flood on a thin link accumulates queueing delay
    exactly like a real egress buffer."""

    regions: Tuple[str, ...] = ()
    links: Mapping[Tuple[str, str], LinkShape] = field(default_factory=dict)
    default: LinkShape = field(default_factory=LinkShape)
    intra: Optional[LinkShape] = None
    jitter_burst: float = 0.0
    burst_multiplier: float = 4.0

    def region_of(self, node: int) -> str:
        if not self.regions:
            return ""
        return self.regions[node % len(self.regions)]

    def link(self, src: int, dst: int) -> Optional[LinkShape]:
        """The shape governing src->dst traffic, None = unshaped."""
        rs, rd = self.region_of(src), self.region_of(dst)
        shape = self.links.get((rs, rd))
        if shape is None:
            shape = self.links.get((rd, rs))
        if shape is None:
            if rs == rd:
                shape = self.intra
            else:
                shape = self.default
        return shape

    # -- spec parsing (CLI flags / config strings / compose env) ------------

    @staticmethod
    def _dur(s: str) -> float:
        """"40ms" / "1.5s" -> seconds; a bare float passes through (clock
        units of whatever layer runs the plan)."""
        s = s.strip()
        if s.endswith("ms"):
            return float(s[:-2]) / 1000.0
        if s.endswith("s"):
            return float(s[:-1])
        return float(s)

    @staticmethod
    def _rate(s: str) -> float:
        """"4mbps" / "512kbps" -> bytes/second; a bare float passes
        through (size units per clock unit)."""
        s = s.strip().lower()
        if s.endswith("mbps"):
            return float(s[:-4]) * 125_000.0
        if s.endswith("kbps"):
            return float(s[:-4]) * 125.0
        if s.endswith("bps"):
            return float(s[:-3]) / 8.0
        return float(s)

    @classmethod
    def _shape_of(cls, spec: str) -> LinkShape:
        """"LAT[/JITTER][@BW]" — e.g. "80ms/8ms@4mbps", "35ms", "3@2"."""
        bw = 0.0
        if "@" in spec:
            spec, _, bw_s = spec.partition("@")
            bw = cls._rate(bw_s)
        lat_s, _, jit_s = spec.partition("/")
        return LinkShape(
            latency=cls._dur(lat_s),
            jitter=cls._dur(jit_s) if jit_s else 0.0,
            bandwidth=bw,
        )

    @classmethod
    def parse(cls, spec: str) -> "LinkShaper":
        """Parse a compact shaper spec, e.g.::

            regions=us,eu,ap,sa;default=80ms/8ms@4mbps;us-eu=35ms;\
intra=2ms;burst=0.01x8

        Items are ';'-separated `key=value` pairs: `regions` (positional
        node->region stripes), `default` (cross-region fallback shape),
        `intra` (same-region shape), `burst=PxM` (jitter burst probability
        P, multiplier M), and `A-B=SHAPE` directed region-pair entries."""
        regions: Tuple[str, ...] = ()
        links: Dict[Tuple[str, str], LinkShape] = {}
        default = LinkShape()
        intra: Optional[LinkShape] = None
        burst_p, burst_m = 0.0, 4.0
        for item in spec.split(";"):
            item = item.strip()
            if not item:
                continue
            key, _, val = item.partition("=")
            if not val:
                raise ValueError(f"shaper spec item {item!r}: expected key=value")
            key = key.strip()
            if key == "regions":
                regions = tuple(r.strip() for r in val.split(",") if r.strip())
            elif key == "default":
                default = cls._shape_of(val)
            elif key == "intra":
                intra = cls._shape_of(val)
            elif key == "burst":
                p_s, _, m_s = val.partition("x")
                burst_p = float(p_s)
                burst_m = float(m_s) if m_s else 4.0
            elif "-" in key:
                a, _, b = key.partition("-")
                links[(a.strip(), b.strip())] = cls._shape_of(val)
            else:
                raise ValueError(f"shaper spec item {item!r}: unknown key")
        return cls(
            regions=regions,
            links=links,
            default=default,
            intra=intra,
            jitter_burst=burst_p,
            burst_multiplier=burst_m,
        )


@dataclass(frozen=True)
class FaultPlan:
    """Seeded adversarial schedule. All probabilities are per-message."""

    seed: int = 0
    drop: float = 0.0        # message silently lost
    duplicate: float = 0.0   # message delivered twice
    delay: float = 0.0       # message deferred (re-queued / timer-delayed)
    reorder: float = 0.0     # message swapped with a random queued one
    delay_span: Tuple[float, float] = (1.0, 16.0)  # sampled delay bounds
    partitions: Tuple[Partition, ...] = ()
    crashes: Tuple[Crash, ...] = ()
    # WAN link shaping (latency matrix / jitter bursts / bandwidth pacing);
    # None = loopback-flat links, the pre-WAN behavior
    shaper: Optional[LinkShaper] = None

    def session(
        self, clock: Optional[Callable[[], float]] = None, salt: int = 0
    ) -> "FaultSession":
        """A live decision stream for one delivery layer. `clock` supplies
        the layer's notion of now (defaults to seconds since creation);
        `salt` decorrelates per-node streams over TCP, where each node owns
        its outbound decisions and there is no global draw order."""
        return FaultSession(self, clock=clock, salt=salt)

    # -- schedule queries (clock-explicit; sessions wrap these) -------------

    def crashed(self, node: int, now: float) -> bool:
        for c in self.crashes:
            if c.node == node and c.at <= now and (
                c.restart is None or now < c.restart
            ):
                return True
        return False

    def partitioned(self, a: int, b: int, now: float) -> bool:
        for p in self.partitions:
            if p.at <= now and (p.heal is None or now < p.heal):
                if (a in p.side_a and b in p.side_b) or (
                    a in p.side_b and b in p.side_a
                ):
                    return True
        return False

    def next_boundary(self, after: float) -> Optional[float]:
        """Earliest schedule edge strictly after `after` — the point a
        quiesced simulator must jump its virtual clock to, so partitions
        heal and crashed nodes restart even with no traffic in flight."""
        edges: List[float] = []
        for c in self.crashes:
            edges.extend(t for t in (c.at, c.restart) if t is not None)
        for p in self.partitions:
            edges.extend(t for t in (p.at, p.heal) if t is not None)
        future = [t for t in edges if t > after]
        return min(future) if future else None

    # -- CLI spec parsing ----------------------------------------------------

    @staticmethod
    def parse_crash(spec: str) -> Crash:
        """"NODE@AT[:RESTART]" — e.g. "1@400:1200", "2@300"."""
        node_s, _, times = spec.partition("@")
        if not times:
            raise ValueError(f"crash spec {spec!r}: expected NODE@AT[:RESTART]")
        at_s, _, restart_s = times.partition(":")
        return Crash(
            node=int(node_s),
            at=float(at_s),
            restart=float(restart_s) if restart_s else None,
        )

    @staticmethod
    def parse_partition(spec: str) -> Partition:
        """"A,B|C,D@AT[:HEAL]" — e.g. "0,1|2,3@300:900"."""
        sides, _, times = spec.partition("@")
        if not times:
            raise ValueError(
                f"partition spec {spec!r}: expected A,B|C,D@AT[:HEAL]"
            )
        a_s, _, b_s = sides.partition("|")
        if not b_s:
            raise ValueError(f"partition spec {spec!r}: missing '|'")
        at_s, _, heal_s = times.partition(":")
        return Partition(
            side_a=frozenset(int(x) for x in a_s.split(",") if x),
            side_b=frozenset(int(x) for x in b_s.split(",") if x),
            at=float(at_s),
            heal=float(heal_s) if heal_s else None,
        )


class FaultSession:
    """One layer's live execution of a FaultPlan: seeded rng + stats.

    All decisions are drawn from a private `random.Random((seed << 20) ^
    salt)`; a layer that replays the same sequence of `decide()` calls
    replays the same faults."""

    def __init__(
        self,
        plan: FaultPlan,
        clock: Optional[Callable[[], float]] = None,
        salt: int = 0,
    ):
        import random

        self.plan = plan
        if clock is None:
            t0 = time.monotonic()
            clock = lambda: time.monotonic() - t0  # noqa: E731
        self._clock = clock
        self.rng = random.Random((plan.seed << 20) ^ (salt & 0xFFFFF))
        self.stats: Dict[str, int] = {
            "dropped": 0,
            "duplicated": 0,
            "delayed": 0,
            "reordered": 0,
            "blocked": 0,   # partition / crash suppression
            "delivered": 0,
            "shaped": 0,    # frames that picked up LinkShaper latency
            "bursts": 0,    # jitter draws that landed in a burst window
        }
        # LinkShaper bandwidth pacer: per directed link, the clock time the
        # link's serializer frees up (frame k queues behind frame k-1)
        self._link_free: Dict[Tuple[int, int], float] = {}

    @property
    def now(self) -> float:
        return self._clock()

    # -- schedule state ------------------------------------------------------

    def crashed(self, node: Optional[int]) -> bool:
        return node is not None and self.plan.crashed(node, self.now)

    def partitioned(self, a: Optional[int], b: Optional[int]) -> bool:
        if a is None or b is None:
            return False
        return self.plan.partitioned(a, b, self.now)

    def link_blocked(self, src: Optional[int], dst: Optional[int]) -> bool:
        return (
            self.crashed(src)
            or self.crashed(dst)
            or self.partitioned(src, dst)
        )

    def next_boundary(self, after: Optional[float] = None) -> Optional[float]:
        return self.plan.next_boundary(self.now if after is None else after)

    # -- per-message decisions ----------------------------------------------

    def decide(
        self, src: Optional[int], dst: Optional[int], size: int = 1
    ) -> List[float]:
        """The fate of one message on the src->dst link: a list of delivery
        delays, one per copy. `[]` = dropped, `[0.0]` = delivered now,
        `[0.0, 0.0]` = duplicated, `[d]` = delivered after `d` time units.
        Unknown endpoints (None) skip link-state checks but still roll the
        probabilistic faults. `size` feeds the LinkShaper bandwidth pacer
        (frame bytes on TCP, a nominal 1 unit in the simulator)."""
        p = self.plan
        if self.link_blocked(src, dst):
            self.stats["blocked"] += 1
            metrics.inc("fault_injected_total", labels={"action": "blocked"})
            return []
        if p.drop > 0 and self.rng.random() < p.drop:
            self.stats["dropped"] += 1
            metrics.inc("fault_injected_total", labels={"action": "drop"})
            return []
        delays = [0.0]
        if p.delay > 0 and self.rng.random() < p.delay:
            lo, hi = p.delay_span
            delays[0] = lo + self.rng.random() * (hi - lo)
            self.stats["delayed"] += 1
            metrics.inc("fault_injected_total", labels={"action": "delay"})
        if p.duplicate > 0 and self.rng.random() < p.duplicate:
            delays.append(0.0)
            self.stats["duplicated"] += 1
            metrics.inc("fault_injected_total", labels={"action": "dup"})
        shaped = self._shape(src, dst, size)
        if shaped > 0:
            # every copy of the frame crosses the same WAN link; shifting
            # them all keeps duplicate spacing intact
            delays = [d + shaped for d in delays]
            self.stats["shaped"] += 1
            metrics.inc("fault_injected_total", labels={"action": "shape"})
            # the seconds those frames were held: what a WAN costs this
            # process's sends, beside how many frames it touched
            metrics.inc("network_shaped_delay_seconds_total", shaped)
        self.stats["delivered"] += 1
        return delays

    def _shape(
        self, src: Optional[int], dst: Optional[int], size: int
    ) -> float:
        """LinkShaper latency for one frame: base + (burst-amplified)
        jitter + bandwidth serialization/queueing delay. 0.0 = unshaped
        link. Jitter draws come from the session rng; pacer state advances
        per call — both deterministic given the call sequence, which is the
        same bit-identity contract the rest of the plan honors."""
        shaper = self.plan.shaper
        if shaper is None or src is None or dst is None or src == dst:
            return 0.0
        link = shaper.link(src, dst)
        if link is None:
            return 0.0
        lat = link.latency
        if link.jitter > 0:
            j = self.rng.random() * link.jitter
            if (
                shaper.jitter_burst > 0
                and self.rng.random() < shaper.jitter_burst
            ):
                j *= shaper.burst_multiplier
                self.stats["bursts"] += 1
            lat += j
        if link.bandwidth > 0 and size > 0:
            now = self.now
            start = max(now, self._link_free.get((src, dst), 0.0))
            done = start + size / link.bandwidth
            self._link_free[(src, dst)] = done
            lat += done - now
        return lat

    def reorder_hit(self) -> bool:
        """One roll of the reorder die (the queue owner does the swap)."""
        if self.plan.reorder <= 0 or self.rng.random() >= self.plan.reorder:
            return False
        self.stats["reordered"] += 1
        metrics.inc("fault_injected_total", labels={"action": "reorder"})
        return True


class TcpFrameFilter:
    """Injectable Hub frame filter executing a FaultPlan over real sockets.

    Installed via `Hub.frame_filter` (or `NetworkManager.install_faults`).
    Outbound frames to a mapped peer run the full link decision — a dropped
    frame still reports success to the sender, so loss is only repairable
    by the message-request/outbox-replay layer, exactly like real loss.
    Inbound frames are suppressed only while WE are crashed (probabilistic
    loss is owned by the sending side, so per-link loss is rolled once).
    """

    def __init__(
        self,
        session: FaultSession,
        my_id: int,
        peer_index: Optional[Callable[[object], Optional[int]]] = None,
    ):
        self.session = session
        self.my_id = my_id
        # peer_index(PeerAddress) -> plan node id (None = unmapped peer:
        # link checks are skipped, probabilistic faults still apply)
        self._peer_index = peer_index or (lambda peer: None)

    def outbound(self, peer, data: bytes) -> List[float]:
        dst = self._peer_index(peer) if peer is not None else None
        return self.session.decide(self.my_id, dst, size=len(data))

    def inbound(self, data: bytes) -> List[float]:
        if self.session.crashed(self.my_id):
            self.session.stats["blocked"] += 1
            metrics.inc("fault_injected_total", labels={"action": "blocked"})
            return []
        return [0.0]


class AdversarialRelayFilter:
    """Hub frame filter modelling a MALICIOUS relay rather than a lossy
    link: the node it is installed on selectively forwards, reorders
    (delays), and replays the signed batch frames it emits. Decisions are
    a pure seeded hash of the frame bytes — two runs replay the identical
    attack (the same determinism contract as FaultPlan and
    consensus/adversary.py). Because frames carry batch signatures, honest
    receivers absorb every replay via signature checks + dedupe, and
    selective forwarding is repaired by the outbox-replay layer; the
    chaos and adversary suites pin that. Composes with an inner filter.
    """

    def __init__(
        self,
        seed: int = 0,
        drop_rate: int = 8,  # silently eat 1-in-N frames
        replay_rate: int = 8,  # send 1-in-N frames twice
        reorder_rate: int = 8,  # delay 1-in-N frames by `delay_s`
        delay_s: float = 0.05,
        inner=None,
    ):
        self.seed = seed
        self.drop_rate = drop_rate
        self.replay_rate = replay_rate
        self.reorder_rate = reorder_rate
        self.delay_s = delay_s
        self.inner = inner
        self.stats = {"forwarded": 0, "dropped": 0, "replayed": 0,
                      "reordered": 0}

    def _h(self, tag: bytes, data: bytes) -> int:
        import hashlib

        h = hashlib.blake2b(digest_size=8)
        h.update(str(self.seed).encode())
        h.update(tag)
        h.update(data)
        return int.from_bytes(h.digest(), "big")

    def outbound(self, peer, data: bytes) -> List[float]:
        if self.inner is not None and not self.inner.outbound(peer, data):
            return []
        if self.drop_rate and self._h(b"drop", data) % self.drop_rate == 0:
            self.stats["dropped"] += 1
            metrics.inc(
                "fault_injected_total", labels={"action": "relay_drop"}
            )
            return []
        if self.replay_rate and self._h(b"dup", data) % self.replay_rate == 0:
            self.stats["replayed"] += 1
            metrics.inc(
                "fault_injected_total", labels={"action": "relay_replay"}
            )
            return [0.0, 0.0]
        if (
            self.reorder_rate
            and self._h(b"ord", data) % self.reorder_rate == 0
        ):
            self.stats["reordered"] += 1
            metrics.inc(
                "fault_injected_total", labels={"action": "relay_reorder"}
            )
            return [self.delay_s]
        self.stats["forwarded"] += 1
        return [0.0]

    def inbound(self, data: bytes) -> List[float]:
        if self.inner is not None:
            return self.inner.inbound(data)
        return [0.0]


class KillSwitch:
    """Hub frame filter that makes a node go dark on command.

    `kill()` suppresses every frame in both directions from that moment
    on — to every peer, the node looks exactly like a SIGKILLed process
    whose kernel still holds the sockets open: sends appear to succeed
    (injected loss must look like the network ate it) and nothing ever
    answers. The tier-1 simulated-kill counterpart of the slow tests'
    real SIGKILL: it exercises the same timeout/failover path without
    the subprocess cost. Composes with an inner filter (e.g. a
    TcpFrameFilter running a FaultPlan) applied while still alive.
    """

    def __init__(self, inner=None):
        self.inner = inner
        self._dead = False

    def kill(self) -> None:
        self._dead = True
        metrics.inc("fault_injected_total", labels={"action": "killswitch"})

    @property
    def dead(self) -> bool:
        return self._dead

    def outbound(self, peer, data: bytes) -> List[float]:
        if self._dead:
            return []
        if self.inner is not None:
            return self.inner.outbound(peer, data)
        return [0.0]

    def inbound(self, data: bytes) -> List[float]:
        if self._dead:
            return []
        if self.inner is not None:
            return self.inner.inbound(data)
        return [0.0]
