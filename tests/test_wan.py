"""WAN survival suite: link shaping, RTT-adaptive recovery, versioned wire.

Covers the three legs of the WAN hardening work:

  * **Link shaping** (network/faults.py LinkShaper): spec grammar, region
    striping, directed link lookup with reversed-pair/default fallback,
    the bandwidth serialization pacer, and the bit-identity contract — a
    same-seed shaped 8-node/2-region devnet must replay its whole
    transcript (block hashes, delivered count, fault tally) exactly.
  * **RTT-adaptive recovery** (network/rtt.py + manager/node): the RFC
    6298 estimator, the bounded `scale()` stretch, the watchdog's
    effective stall timeout, and the per-peer reconnect token bucket that
    rations strike-3 forced reconnects.
  * **Versioned wire + rolling upgrades** (network/wire.py LTRX block):
    handshake roundtrip, tail layout interop against an INLINE copy of
    the pre-handshake decoder (the downgrade case), the adjacency
    compatibility matrix, version gating of too-new kinds, and the
    full rolling-upgrade drill — a 6-node loopback TCP fleet rolled
    node-by-node under traffic must stay `/healthz` ok, miss zero fleet
    eras, and commit bit-identical block headers to a no-upgrade control.

Marked `wan` (make test-wan); the fleet drills are additionally `slow`.
"""
from __future__ import annotations

import asyncio
import os
import random
import zlib

import pytest

from lachain_tpu.core.devnet import Devnet
from lachain_tpu.crypto import ecdsa
from lachain_tpu.network import wire
from lachain_tpu.network.faults import FaultPlan, LinkShape, LinkShaper
from lachain_tpu.network.manager import NetworkManager
from lachain_tpu.network.rtt import RttTracker
from lachain_tpu.utils.serialization import Reader

pytestmark = pytest.mark.wan

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Rng:
    def __init__(self, seed):
        self._r = random.Random(seed)

    def randbelow(self, k):
        return self._r.randrange(k)


def _priv(seed=11):
    return ecdsa.generate_private_key(_Rng(seed))


# ---------------------------------------------------------------------------
# link shaper: spec grammar + matrix lookup + pacer
# ---------------------------------------------------------------------------


def test_shaper_spec_parses_full_grammar():
    sh = LinkShaper.parse(
        "regions=us,eu,ap,sa;default=80ms/8ms@4mbps;us-eu=35ms;"
        "intra=2ms;burst=0.01x8"
    )
    assert sh.regions == ("us", "eu", "ap", "sa")
    assert sh.default.latency == pytest.approx(0.080)
    assert sh.default.jitter == pytest.approx(0.008)
    assert sh.default.bandwidth == pytest.approx(500_000.0)  # 4mbps in B/s
    assert sh.links[("us", "eu")].latency == pytest.approx(0.035)
    assert sh.intra.latency == pytest.approx(0.002)
    assert sh.jitter_burst == pytest.approx(0.01)
    assert sh.burst_multiplier == pytest.approx(8.0)


def test_shaper_spec_rejects_garbage():
    with pytest.raises(ValueError):
        LinkShaper.parse("nonsense")
    with pytest.raises(ValueError):
        LinkShaper.parse("bogus=1")


def test_region_striping_and_directed_lookup():
    sh = LinkShaper(
        regions=("us", "eu"),
        links={
            ("us", "eu"): LinkShape(latency=3.0),
            ("eu", "us"): LinkShape(latency=5.0),  # asymmetric return path
        },
        default=LinkShape(latency=9.0),
    )
    # positional striping: node i -> regions[i % len]
    assert [sh.region_of(i) for i in range(4)] == ["us", "eu", "us", "eu"]
    # directed entries resolve per direction
    assert sh.link(0, 1).latency == 3.0
    assert sh.link(1, 0).latency == 5.0
    # intra-region links are unshaped unless intra/explicit entry exists
    assert sh.link(0, 2) is None
    sh2 = LinkShaper(regions=("us", "eu"), intra=LinkShape(latency=1.0))
    assert sh2.link(0, 2).latency == 1.0
    # reversed-pair fallback when only one direction is specified
    sh3 = LinkShaper(
        regions=("us", "eu"), links={("us", "eu"): LinkShape(latency=7.0)}
    )
    assert sh3.link(1, 0).latency == 7.0


def test_bandwidth_pacer_accumulates_queueing_delay():
    sh = LinkShaper(
        regions=("a", "b"), default=LinkShape(latency=0.0, bandwidth=100.0)
    )
    t = [0.0]
    s = FaultPlan(seed=1, shaper=sh).session(clock=lambda: t[0])
    # back-to-back frames queue behind the link serializer (100 units/s)
    assert s.decide(0, 1, size=100) == [pytest.approx(1.0)]
    assert s.decide(0, 1, size=100) == [pytest.approx(2.0)]
    # the reverse direction is its own serializer (asymmetric by design)
    assert s.decide(1, 0, size=100) == [pytest.approx(1.0)]
    # once the link drains, queueing resets
    t[0] = 10.0
    assert s.decide(0, 1, size=100) == [pytest.approx(1.0)]
    assert s.stats["shaped"] == 4


def test_same_seed_same_shaping_stream():
    sh = LinkShaper.parse("regions=a,b;default=3/2;burst=0.2x4")
    plan = FaultPlan(seed=5, shaper=sh)

    def stream():
        s = plan.session(clock=lambda: 0.0)
        fates = [s.decide(i % 2, (i + 1) % 2) for i in range(200)]
        return fates, dict(s.stats)

    assert stream() == stream()
    assert stream()[1]["bursts"] > 0


def test_shaped_two_region_fleet_is_bit_identical():
    """Satellite 2: a shaped 8-node/2-region devnet replays its full
    transcript bit-identically across two same-seed runs — the property
    that keeps shaped chaos scenarios as replayable as unshaped ones.
    Latencies are in the simulator's virtual tick units (bare floats)."""
    sh = LinkShaper.parse("regions=us,eu;default=3/2;intra=1;burst=0.05x4")
    runs = []
    for _ in range(2):
        d = Devnet(n=8, f=2, seed=13, link_shaper=sh)
        blocks = d.run_eras(1, 2)
        runs.append(
            (
                [b.hash() for b in blocks],
                d.net.delivered_count,
                dict(d.net.faults.stats),
            )
        )
    assert runs[0] == runs[1]
    # the shaper actually fired; this is not an unshaped rerun
    assert runs[0][2]["shaped"] > 0


@pytest.mark.parametrize("caller", ["cli", "fleet"])
def test_install_wan_shaper_decides_as_the_two_old_blocks_did(caller):
    """cli._build_node and TcpFleet._install_shaper each carried this
    block — parse (the CLI only), install_faults, one map_fault_peer per
    validator; NetworkManager.install_wan_shaper is now the one copy. For
    one seed, the session it installs holds the same frames to the same
    peers for the same time."""
    from lachain_tpu.network.hub import PeerAddress

    spec = "regions=us,eu,ap,sa;default=40ms/5ms;us-eu=25ms/3ms;intra=2ms/1ms;burst=0.01x8"
    pubs = [ecdsa.public_key_bytes(_priv(100 + j)) for j in range(8)]
    me, seed = 5, 225

    old = NetworkManager(_priv(100 + me))
    old_filt = old.install_faults(
        FaultPlan(seed=seed, shaper=LinkShaper.parse(spec)), me
    )
    for j, pub in enumerate(pubs):
        old.map_fault_peer(pub, j)

    new = NetworkManager(_priv(100 + me))
    new_filt = new.install_wan_shaper(
        spec if caller == "cli" else LinkShaper.parse(spec), me, pubs, seed
    )
    assert new.hub.frame_filter is new_filt

    rng = random.Random(9)
    frames = [
        (PeerAddress(pubs[rng.randrange(8)], "127.0.0.1", 1), bytes(rng.randrange(40, 900)))
        for _ in range(600)
    ]
    stranger = PeerAddress(b"\x02" * 33, "127.0.0.1", 1)  # no validator: unshaped
    frames.insert(300, (stranger, b"x" * 50))
    got = [new_filt.outbound(peer, data) for peer, data in frames]
    assert got == [old_filt.outbound(peer, data) for peer, data in frames]
    assert got[300] == [0.0] and sum(d == [0.0] for d in got) > 1  # frames to itself too
    assert all(d[0] >= 0.002 for d in got if d != [0.0])
    assert new_filt.session.stats == old_filt.session.stats
    assert new_filt.session.stats["shaped"] > 400


def test_native_engine_rejects_shaper_plans():
    sh = LinkShaper.parse("regions=a,b;default=3")
    with pytest.raises(ValueError, match="link shaper"):
        Devnet(n=4, f=1, seed=1, engine="native", link_shaper=sh)


# ---------------------------------------------------------------------------
# RTT estimation + adaptive timeout scaling
# ---------------------------------------------------------------------------


def test_rtt_ewma_rto_and_unsolicited_replies():
    t = [0.0]
    rtt = RttTracker(clock=lambda: t[0])
    rtt.note_sent(b"p1")
    t[0] = 0.1
    assert rtt.note_reply(b"p1") == pytest.approx(0.1)
    assert rtt.srtt(b"p1") == pytest.approx(0.1)
    # second sample smooths per RFC 6298 (alpha=1/8)
    t[0] = 1.0
    rtt.note_sent(b"p1")
    t[0] = 1.3
    rtt.note_reply(b"p1")
    assert rtt.srtt(b"p1") == pytest.approx(0.875 * 0.1 + 0.125 * 0.3)
    # unsolicited replies are ignored; unmeasured peers get the RTO floor
    assert rtt.note_reply(b"p2") is None
    assert rtt.rto(b"p2") == pytest.approx(0.2)
    assert rtt.rto(b"p1") >= rtt.srtt(b"p1")
    assert rtt.snapshot()[b"p1"[:4].hex()]["samples"] == 2


def test_rtt_scale_is_bounded():
    t = [0.0]
    rtt = RttTracker(clock=lambda: t[0])
    # no samples: base passes through untouched
    assert rtt.scale(1.0) == 1.0
    # a genuinely slow fleet stretches timeouts, but never past 4x — the
    # watchdog must stay armed no matter how bad the links get
    rtt.note_sent(b"p")
    t[0] = 5.0
    rtt.note_reply(b"p")
    assert rtt.scale(1.0) == pytest.approx(4.0)
    assert rtt.scale(100.0) == pytest.approx(100.0)  # 20*srtt below base


def test_node_stall_timeout_scales_with_rtt():
    from lachain_tpu.consensus.keys import trusted_key_gen
    from lachain_tpu.core.node import Node

    pub, privs = trusted_key_gen(4, 1, rng=_Rng(3))
    node = Node(index=0, public_keys=pub, private_keys=privs[0], chain_id=225)
    base = node.stall_timeout
    assert node.effective_stall_timeout == base
    t = [0.0]
    node.network.rtt = RttTracker(clock=lambda: t[0])
    node.network.rtt.note_sent(b"q")
    t[0] = 60.0  # pathological RTT: hits the 4x cap
    node.network.rtt.note_reply(b"q")
    assert node.effective_stall_timeout == pytest.approx(4.0 * base)


def test_reconnect_token_bucket_caps_forced_reconnects():
    mgr = NetworkManager(_priv())
    pub = b"\x02" * 33
    # capacity 2: two reconnects pass, the third is suppressed
    assert mgr._reconnect_allowed(pub, 0.0)
    assert mgr._reconnect_allowed(pub, 0.0)
    assert not mgr._reconnect_allowed(pub, 0.0)
    # refill is one token per reconnect_min_interval
    assert mgr._reconnect_allowed(pub, mgr.reconnect_min_interval + 1.0)
    assert not mgr._reconnect_allowed(pub, mgr.reconnect_min_interval + 2.0)
    # per-peer buckets: an exhausted peer does not starve another
    assert mgr._reconnect_allowed(b"\x03" * 33, 0.0)


def test_reconnect_interval_stretches_with_rtt():
    mgr = NetworkManager(_priv())
    t = [0.0]
    mgr.rtt = RttTracker(clock=lambda: t[0])
    mgr.rtt.note_sent(b"q")
    t[0] = 2.0  # srtt 2s -> scale(30) = 20*2 = 40s refill interval
    mgr.rtt.note_reply(b"q")
    pub = b"\x04" * 33
    assert mgr._reconnect_allowed(pub, 0.0)
    assert mgr._reconnect_allowed(pub, 0.0)
    # 35s is past the loopback-tuned 30s interval but short of the
    # RTT-stretched 40s one: still suppressed
    assert not mgr._reconnect_allowed(pub, 35.0)
    assert mgr._reconnect_allowed(pub, 45.0)


# ---------------------------------------------------------------------------
# versioned wire: handshake block, tail layout, compat matrix, gating
# ---------------------------------------------------------------------------


def _consensus_raw(era: int) -> wire.NetworkMessage:
    """A consensus-kind message with just the era prefix the batch
    trailer logic reads — payload bytes are opaque to the tail tests."""
    return wire.NetworkMessage(
        kind=wire.KIND_CONSENSUS,
        body=era.to_bytes(8, "big", signed=True) + b"payload",
    )


def test_handshake_roundtrip_and_reject():
    hs = wire.WireHandshake(2, 1, wire.FEATURES_DEFAULT)
    assert wire.WireHandshake.decode(hs.encode()) == hs
    assert wire.WireHandshake.decode(b"XXXX" + hs.encode()[4:]) is None
    assert wire.WireHandshake.decode(hs.encode()[:-1]) is None
    assert wire.WireHandshake.decode(b"") is None


def test_batch_tail_carries_handshake_and_trailer():
    f = wire.MessageFactory(_priv())
    b = f.batch([_consensus_raw(7)])
    hs = b.handshake()
    assert hs is not None
    assert hs.wire_version == wire.WIRE_VERSION
    assert hs.engine_version == wire.ENGINE_VERSION
    assert hs.features == wire.FEATURES_DEFAULT
    # the trace trailer stays the OUTERMOST suffix (legacy parsers read
    # the final 29 bytes blind)
    tr = b.trace_trailer()
    assert tr is not None and tr[1] == 7
    assert b.verify()
    # non-consensus batch: no trailer, handshake still at the tail
    b2 = f.batch([wire.ping_request(5)])
    assert b2.trace_trailer() is None
    assert b2.handshake() is not None
    # legacy sender: no handshake block at all
    f.handshake = False
    assert f.batch([wire.ping_request(5)]).handshake() is None


# Inline copy of the PRE-handshake decoder (wire.py before the LTRX
# block): zlib stream + optional 29-byte LTRC trailer as the outermost
# content suffix, any other tail bytes ignored. Kept VERBATIM-shaped on
# purpose — it models what an unupgraded node actually runs, so these
# asserts are the downgrade half of the rolling-upgrade interop story.


def _legacy_decode_messages(batch: wire.MessageBatch):
    d = zlib.decompressobj()
    raw = d.decompress(batch.content, 1 << 26)
    assert not d.unconsumed_tail and d.eof
    r = Reader(raw)
    out = [wire.NetworkMessage.decode_from(r) for _ in range(r.u32())]
    r.assert_eof()
    return out


def _legacy_trace_trailer(batch: wire.MessageBatch):
    c = batch.content
    if len(c) < 29:
        return None
    tail = c[len(c) - 29:]
    if tail[:4] != b"LTRC" or tail[4] != 1:
        return None
    era = int.from_bytes(tail[13:21], "big", signed=True)
    return tail[5:13], era, tail[21:29]


def test_v2_batches_interop_with_legacy_decoder():
    """Downgrade interop: an upgraded (handshake-advertising) sender's
    batches decode cleanly on the pre-handshake decoder, trailer
    included — and a legacy sender's batches decode on the new one."""
    f = wire.MessageFactory(_priv())
    msgs = [_consensus_raw(4), wire.ping_request(9)]
    b = f.batch(msgs)
    legacy = _legacy_decode_messages(b)
    assert [(m.kind, m.body) for m in legacy] == [
        (m.kind, m.body) for m in msgs
    ]
    trailer = _legacy_trace_trailer(b)
    assert trailer is not None and trailer[1] == 4
    # the other direction: legacy batch through the new decoder
    f2 = wire.MessageFactory(_priv(12))
    f2.handshake = False
    b2 = f2.batch(msgs)
    assert [(m.kind, m.body) for m in b2.messages()] == [
        (m.kind, m.body) for m in msgs
    ]
    assert b2.handshake() is None
    assert b2.trace_trailer() is not None


def test_compat_matrix_is_adjacency():
    assert wire.compatible(1, 2)
    assert wire.compatible(2, 2)
    assert wire.compatible(2, 1)
    assert not wire.compatible(1, 3)
    # snapshot kinds are the v2 vocabulary; everything else is v1
    assert wire.KIND_MIN_WIRE[wire.KIND_SNAPSHOT_REQUEST] == 2
    assert wire.KIND_MIN_WIRE[wire.KIND_SNAPSHOT_REPLY] == 2
    assert wire.KIND_MIN_WIRE[wire.KIND_CONSENSUS] == 1


def test_version_gating_only_for_advertised_older_peers():
    mgr = NetworkManager(_priv())
    pub = b"\x05" * 33
    snap = wire.NetworkMessage(kind=wire.KIND_SNAPSHOT_REQUEST, body=b"")
    # a peer that never advertised is assumed legacy but NOT gated —
    # pre-handshake fleets must behave exactly as before the upgrade
    assert not mgr._version_gated(pub, snap)
    # a peer that EXPLICITLY advertised wire v1 is protected from
    # v2-only kinds (its decoder would raise on them)...
    mgr.peer_versions[pub] = wire.WireHandshake(1, 1, 0)
    assert mgr._version_gated(pub, snap)
    assert mgr.wire_version_of(pub) == 1
    # ...but v1 kinds still flow
    assert not mgr._version_gated(pub, wire.ping_request(1))
    # an up-to-date peer gets everything
    mgr.peer_versions[pub] = wire.WireHandshake(2, 1, wire.FEATURES_DEFAULT)
    assert not mgr._version_gated(pub, snap)


# ---------------------------------------------------------------------------
# rolling-upgrade drill (slow: boots real loopback TCP fleets)
# ---------------------------------------------------------------------------


def _drill_txs(user_priv, chain_id, nonce0, k):
    from lachain_tpu.core.types import Transaction, sign_transaction

    return [
        sign_transaction(
            Transaction(
                to=b"\x0d" * 20,
                value=1 + j,
                nonce=nonce0 + j,
                gas_price=1,
                gas_limit=21000,
            ),
            user_priv,
            chain_id,
        )
        for j in range(k)
    ]


@pytest.mark.slow
def test_rolling_upgrade_drill_matches_control():
    """Satellite 3: a 6-node fleet rolls node-by-node from the legacy
    wire onto the LTRX wire under open-loop traffic. Zero-downtime gate:
    /healthz stays ok at every era checkpoint, the FLEET misses no eras,
    and the committed block headers are bit-identical to a no-upgrade
    control run fed the same transactions."""
    from lachain_tpu.core.fleet import TcpFleet

    N = 6

    async def run(roll: bool):
        user_priv = _priv(5)
        user_addr = ecdsa.address_from_public_key(
            ecdsa.public_key_bytes(user_priv)
        )
        fleet = TcpFleet(
            n=N,
            f=1,
            seed=21,
            txs_per_block=64,
            initial_balances={user_addr: 10**21},
            legacy_wire=roll,
        )
        hashes = []
        await fleet.start()
        try:
            nonce = 0
            era = 0

            async def one_era():
                nonlocal era, nonce
                era += 1
                await fleet.submit_and_settle(
                    _drill_txs(user_priv, fleet.chain_id, nonce, 3)
                )
                nonce += 3
                hashes.append(await fleet.run_era(era))
                statuses = fleet.health_statuses()
                assert all(s == "ok" for s in statuses.values()), statuses

            await one_era()  # warmup era, whole fleet up
            if roll:
                for i in range(N):
                    await fleet.take_down(i)
                    await one_era()  # survivors commit with node i out
                    await fleet.bring_up(i, next_era=era + 1)
                # every node ended up advertising the new wire
                versions = fleet.wire_versions()
                assert all(
                    v == wire.WIRE_VERSION for v in versions.values()
                ), versions
                # per-node misses are exactly the one era each sat out
                assert sorted(fleet.missed_eras) == list(range(N))
                assert all(
                    len(v) == 1 for v in fleet.missed_eras.values()
                ), fleet.missed_eras
            else:
                for _ in range(N):
                    await one_era()
            await one_era()  # cooldown era, whole fleet up
        finally:
            await fleet.stop()
        return hashes

    drill = asyncio.run(run(True))
    control = asyncio.run(run(False))
    # every era committed in both runs (zero FLEET missed eras), and the
    # chain content is independent of the upgrade happening at all
    assert len(drill) == N + 2
    assert drill == control
