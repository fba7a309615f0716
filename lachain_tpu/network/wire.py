"""Network wire format: consensus payload codec + message kinds + batches.

Parity with the reference's proto layer
(/root/reference/src/Lachain.Proto/networking.proto — `NetworkMessage` oneof
of 7 kinds, `MessageBatch{sender, signature, content}`;
consensus.proto:77-91 — `ConsensusMessage` oneof of 9 payloads) using the
framework's fixed-width codec instead of protobuf.

A `MessageBatch` is the unit of transport: sender's compressed message list,
ECDSA-signed (reference MessageFactory.cs:80-103, verified at
NetworkManagerBase.cs:117-122; Deflate compression per HubConnector.cs:98).
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import List, Optional, Tuple

from ..consensus import messages as M
from ..core.types import Block, SignedTransaction
from ..crypto import ecdsa
from ..crypto.hashes import keccak256
from ..utils import tracing
from ..utils.serialization import (
    Reader,
    write_bytes,
    write_bytes_list,
    write_i64,
    write_u32,
    write_u64,
)

# ---------------------------------------------------------------------------
# consensus payload codec (the ConsensusMessage oneof)
# ---------------------------------------------------------------------------

_VAL, _ECHO, _READY, _BVAL, _AUX, _CONF, _COIN, _DEC, _HDR = range(1, 10)


def _enc_rbc(rbc: M.ReliableBroadcastId) -> bytes:
    return write_i64(rbc.era) + write_u32(rbc.sender_id)


def _dec_rbc(r: Reader) -> M.ReliableBroadcastId:
    return M.ReliableBroadcastId(era=r.i64(), sender_id=r.u32())


def _enc_bb(bb: M.BinaryBroadcastId) -> bytes:
    return write_i64(bb.era) + write_i64(bb.agreement) + write_i64(bb.epoch)


def _dec_bb(r: Reader) -> M.BinaryBroadcastId:
    return M.BinaryBroadcastId(era=r.i64(), agreement=r.i64(), epoch=r.i64())


def encode_payload(p) -> bytes:
    if isinstance(p, M.ValMessage):
        return (
            bytes([_VAL])
            + _enc_rbc(p.rbc)
            + write_bytes(p.root)
            + write_bytes_list(list(p.branch))
            + write_bytes(p.shard)
            + write_u32(p.shard_index)
        )
    if isinstance(p, M.EchoMessage):
        return (
            bytes([_ECHO])
            + _enc_rbc(p.rbc)
            + write_bytes(p.root)
            + write_bytes_list(list(p.branch))
            + write_bytes(p.shard)
            + write_u32(p.shard_index)
        )
    if isinstance(p, M.ReadyMessage):
        return bytes([_READY]) + _enc_rbc(p.rbc) + write_bytes(p.root)
    if isinstance(p, M.BValMessage):
        return bytes([_BVAL]) + _enc_bb(p.bb) + bytes([1 if p.value else 0])
    if isinstance(p, M.AuxMessage):
        return bytes([_AUX]) + _enc_bb(p.bb) + bytes([1 if p.value else 0])
    if isinstance(p, M.ConfMessage):
        mask = (1 if False in p.values else 0) | (2 if True in p.values else 0)
        return bytes([_CONF]) + _enc_bb(p.bb) + bytes([mask])
    if isinstance(p, M.CoinMessage):
        c = p.coin
        return (
            bytes([_COIN])
            + write_i64(c.era)
            + write_i64(c.agreement)
            + write_i64(c.epoch)
            + write_bytes(p.share)
        )
    if isinstance(p, M.DecryptedMessage):
        return (
            bytes([_DEC])
            + write_i64(p.hb.era)
            + write_u32(p.share_id)
            + write_bytes(p.payload)
        )
    if isinstance(p, M.SignedHeaderMessage):
        return (
            bytes([_HDR])
            + write_i64(p.root.era)
            + write_bytes(p.header_bytes)
            + write_bytes(p.signature)
        )
    raise TypeError(f"unencodable payload {type(p)}")


def decode_payload(data: bytes):
    r = Reader(data)
    tag = r.raw(1)[0]
    if tag in (_VAL, _ECHO):
        rbc = _dec_rbc(r)
        root = r.bytes_()
        branch = tuple(r.bytes_list())
        shard = r.bytes_()
        idx = r.u32()
        cls = M.ValMessage if tag == _VAL else M.EchoMessage
        return cls(rbc=rbc, root=root, branch=branch, shard=shard, shard_index=idx)
    if tag == _READY:
        return M.ReadyMessage(rbc=_dec_rbc(r), root=r.bytes_())
    if tag == _BVAL:
        return M.BValMessage(bb=_dec_bb(r), value=r.raw(1)[0] != 0)
    if tag == _AUX:
        return M.AuxMessage(bb=_dec_bb(r), value=r.raw(1)[0] != 0)
    if tag == _CONF:
        bb = _dec_bb(r)
        mask = r.raw(1)[0]
        vals = frozenset(
            v for v, bit in ((False, 1), (True, 2)) if mask & bit
        )
        return M.ConfMessage(bb=bb, values=vals)
    if tag == _COIN:
        coin = M.CoinId(era=r.i64(), agreement=r.i64(), epoch=r.i64())
        return M.CoinMessage(coin=coin, share=r.bytes_())
    if tag == _DEC:
        hb = M.HoneyBadgerId(era=r.i64())
        return M.DecryptedMessage(hb=hb, share_id=r.u32(), payload=r.bytes_())
    if tag == _HDR:
        root = M.RootProtocolId(era=r.i64())
        return M.SignedHeaderMessage(
            root=root, header_bytes=r.bytes_(), signature=r.bytes_()
        )
    raise ValueError(f"unknown payload tag {tag}")


# ---------------------------------------------------------------------------
# network messages (the NetworkMessage oneof) + priorities
# ---------------------------------------------------------------------------

KIND_CONSENSUS = 1
KIND_PING_REQUEST = 2
KIND_PING_REPLY = 3
KIND_SYNC_BLOCKS_REQUEST = 4
KIND_SYNC_BLOCKS_REPLY = 5
KIND_SYNC_POOL_REQUEST = 6
KIND_SYNC_POOL_REPLY = 7
KIND_FAST_SYNC_REQUEST = 8
KIND_FAST_SYNC_REPLY = 9
KIND_TRIE_NODES_REQUEST = 10
KIND_TRIE_NODES_REPLY = 11
KIND_PEERS_REQUEST = 12
KIND_PEERS_REPLY = 13
# relay/NAT traversal (role of the reference's hub-relay network,
# Hub/HubConnector.cs:26-105): a node with no dialable address registers
# with a public relay and receives traffic wrapped in relay_forward
# messages, delivered back over its own outbound TCP connection
KIND_RELAY_REGISTER = 14
KIND_RELAY_FORWARD = 15
# consensus retransmission (role of the reference node's message-request/
# resend layer): HBBFT protocols never retransmit, so a node missing
# messages for an era re-requests them; the receiver replays its per-era
# outbox (consensus/era.py) addressed to the requester
KIND_MESSAGE_REQUEST = 16
# request-id variants of the trie-node exchange (reference
# RequestManager.cs: every batch carries a request id so late/duplicate
# replies can never be attributed to the wrong in-flight batch). The
# id-less kinds 10/11 stay served for older peers; new clients only
# send 17 and consume 18.
KIND_TRIE_NODES_REQUEST_ID = 17
KIND_TRIE_NODES_REPLY_ID = 18
# snapshot shipping: cursor-paged pull of a peer's raw trie-node rows
# (the bulk alternative to node-by-node download; the db export/import
# dump format reframed as a wire exchange). Pull-based paging keeps the
# receiver in control: one page in flight per request id, resumable at
# the cursor from a different peer mid-stream.
KIND_SNAPSHOT_REQUEST = 19
KIND_SNAPSHOT_REPLY = 20

# reference NetworkMessagePriority: replies < consensus < pool sync
PRIORITY = {
    KIND_PING_REPLY: 0,
    KIND_SYNC_BLOCKS_REPLY: 0,
    KIND_SYNC_POOL_REPLY: 0,
    KIND_FAST_SYNC_REQUEST: 2,
    KIND_FAST_SYNC_REPLY: 0,
    KIND_TRIE_NODES_REQUEST: 2,
    KIND_TRIE_NODES_REPLY: 0,
    KIND_TRIE_NODES_REQUEST_ID: 2,
    KIND_TRIE_NODES_REPLY_ID: 0,
    KIND_SNAPSHOT_REQUEST: 2,
    KIND_SNAPSHOT_REPLY: 0,
    KIND_CONSENSUS: 1,
    KIND_PING_REQUEST: 2,
    KIND_SYNC_BLOCKS_REQUEST: 2,
    KIND_SYNC_POOL_REQUEST: 2,
    KIND_PEERS_REQUEST: 2,
    KIND_PEERS_REPLY: 2,
    KIND_RELAY_REGISTER: 1,
    KIND_RELAY_FORWARD: 1,  # carries consensus traffic: consensus priority
    KIND_MESSAGE_REQUEST: 1,  # unblocks consensus: consensus priority
}


@dataclass(frozen=True)
class NetworkMessage:
    kind: int
    body: bytes  # kind-specific encoding

    def encode(self) -> bytes:
        return bytes([self.kind]) + write_bytes(self.body)

    @classmethod
    def decode_from(cls, r: Reader) -> "NetworkMessage":
        kind = r.raw(1)[0]
        if kind not in PRIORITY:
            raise ValueError(f"unknown message kind {kind}")
        return cls(kind=kind, body=r.bytes_())


def consensus_msg(era: int, payload) -> NetworkMessage:
    return NetworkMessage(
        KIND_CONSENSUS, write_i64(era) + encode_payload(payload)
    )


def parse_consensus(msg: NetworkMessage) -> Tuple[int, object]:
    r = Reader(msg.body)
    era = r.i64()
    return era, decode_payload(r.rest())


def message_request(era: int) -> NetworkMessage:
    """Ask a peer to replay its consensus outbox for `era` to us — the
    recovery path for a wedged era (a lost RBC ECHO is unrecoverable for
    its slot without retransmission). Replays are rate-limited per
    (peer, era) on the serving side."""
    return NetworkMessage(KIND_MESSAGE_REQUEST, write_i64(era))


def parse_message_request(msg: NetworkMessage) -> int:
    r = Reader(msg.body)
    era = r.i64()
    r.assert_eof()
    return era


def ping_request(height: int) -> NetworkMessage:
    return NetworkMessage(KIND_PING_REQUEST, write_u64(height))


def ping_reply(height: int) -> NetworkMessage:
    return NetworkMessage(KIND_PING_REPLY, write_u64(height))


def parse_height(msg: NetworkMessage) -> int:
    return Reader(msg.body).u64()


def sync_blocks_request(start: int, count: int) -> NetworkMessage:
    return NetworkMessage(
        KIND_SYNC_BLOCKS_REQUEST, write_u64(start) + write_u32(count)
    )


def parse_sync_blocks_request(msg: NetworkMessage) -> Tuple[int, int]:
    r = Reader(msg.body)
    return r.u64(), r.u32()


def sync_blocks_reply(blocks: List[Tuple[Block, List[SignedTransaction]]]) -> NetworkMessage:
    out = write_u32(len(blocks))
    for block, txs in blocks:
        out += write_bytes(block.encode())
        out += write_bytes_list([t.encode() for t in txs])
    return NetworkMessage(KIND_SYNC_BLOCKS_REPLY, out)


def parse_sync_blocks_reply(
    msg: NetworkMessage,
) -> List[Tuple[Block, List[SignedTransaction]]]:
    r = Reader(msg.body)
    out = []
    for _ in range(r.u32()):
        block = Block.decode(r.bytes_())
        txs = [SignedTransaction.decode(t) for t in r.bytes_list()]
        out.append((block, txs))
    return out


def sync_pool_request(hashes: List[bytes]) -> NetworkMessage:
    return NetworkMessage(KIND_SYNC_POOL_REQUEST, write_bytes_list(hashes))


def parse_sync_pool_request(msg: NetworkMessage) -> List[bytes]:
    return Reader(msg.body).bytes_list()


def sync_pool_reply(txs: List[SignedTransaction]) -> NetworkMessage:
    return NetworkMessage(
        KIND_SYNC_POOL_REPLY, write_bytes_list([t.encode() for t in txs])
    )


def parse_sync_pool_reply(msg: NetworkMessage) -> List[SignedTransaction]:
    return [SignedTransaction.decode(t) for t in Reader(msg.body).bytes_list()]


# ---------------------------------------------------------------------------
# signed batches
# ---------------------------------------------------------------------------

# Trace-context trailer (fleet observability): a fixed-width suffix INSIDE
# `content`, appended AFTER the zlib stream ends. Placement is the whole
# design: `messages()` decompresses with a decompressobj, which stops at
# the stream end and leaves trailing bytes in `unused_data` — so a
# trailer-free decoder (any pre-trailer build) accepts the frame
# unchanged, and the batch signature (over the full content bytes) covers
# the trailer for free. DESIGN DIVERGENCE from a trailer "past the signed
# region": appending after the signature'd field would trip the old
# decoder's assert_eof and break mixed-version interop — inside-content
# placement is the variant old peers actually tolerate, and an
# authenticated trace context is strictly better than an unauthenticated
# one. Layout (29 bytes):
#   magic "LTRC" (4) | version 0x01 (1) | origin (8) | era i64 (8) |
#   trace id (8)
# origin = keccak256(sender pubkey)[:8]; trace id =
# era_trace_id(sender, era) — both deterministic, so the fleet merger can
# recompute them from the era report alone and match receiver-side
# wire.trace_ctx instants without any coordination.
TRACE_TRAILER_MAGIC = b"LTRC"
TRACE_TRAILER_VERSION = 1
TRACE_TRAILER_LEN = 4 + 1 + 8 + 8 + 8

# Wire/engine version handshake (rolling upgrades): the LTRC trick,
# generalized. A second fixed-width block rides in the same
# ignored-by-old-decoders tail region of `content`, BEFORE the trace
# trailer (the trailer must stay the outermost suffix: legacy
# `trace_trailer()` parses the last 29 bytes unconditionally, so any block
# appended after it would break trace parsing on un-upgraded peers).
# Tail layout, outermost last:
#   <zlib stream> [LTRX handshake, 13 bytes] [LTRC trailer, 29 bytes]
# Handshake layout (13 bytes):
#   magic "LTRX" (4) | hs version 0x01 (1) | wire_version u16 |
#   engine_version u16 | feature bits u32
# Signed for free (batch signature covers content), invisible to
# pre-handshake decoders, and piggybacked on every batch — no extra
# round-trip, and a restarted peer's version is re-learned on its first
# frame.
HANDSHAKE_MAGIC = b"LTRX"
HANDSHAKE_VERSION = 1
HANDSHAKE_LEN = 4 + 1 + 2 + 2 + 4

# The compatibility matrix. WIRE_VERSION is the frame/kind vocabulary;
# ENGINE_VERSION is the consensus engine generation (informational — mixed
# engines are expected mid-upgrade and never gate traffic). The contract
# that makes node-by-node rolling upgrades safe is ADJACENCY: version v
# interoperates with v±1, so a fleet may straddle two consecutive wire
# versions during a roll but never three. Skipping a wire version requires
# two rolls.
WIRE_VERSION = 2  # v1 = pre-handshake (implicit); v2 adds LTRX + snapshots
ENGINE_VERSION = 1
MIN_COMPAT_WIRE_VERSION = 1

# feature bits (advertised capabilities, not gates)
FEATURE_TRACE_TRAILER = 1 << 0
FEATURE_SNAPSHOT_SYNC = 1 << 1
FEATURES_DEFAULT = FEATURE_TRACE_TRAILER | FEATURE_SNAPSHOT_SYNC

# Minimum wire version that understands each kind. Kinds absent from a
# peer's vocabulary raise in its decode_from — so a sender must not emit
# them toward a peer that has ADVERTISED an older version. Peers that have
# never advertised (legacy, pre-handshake) are assumed version 1.
KIND_MIN_WIRE = {k: 1 for k in PRIORITY}
KIND_MIN_WIRE[KIND_SNAPSHOT_REQUEST] = 2
KIND_MIN_WIRE[KIND_SNAPSHOT_REPLY] = 2


def compatible(a: int, b: int) -> bool:
    """True iff wire versions `a` and `b` may share a link (adjacency
    contract: |a-b| <= 1)."""
    return abs(a - b) <= 1


@dataclass(frozen=True)
class WireHandshake:
    """A peer's advertised versions, parsed off its batch tail."""

    wire_version: int
    engine_version: int
    features: int

    def encode(self) -> bytes:
        return (
            HANDSHAKE_MAGIC
            + bytes([HANDSHAKE_VERSION])
            + self.wire_version.to_bytes(2, "big")
            + self.engine_version.to_bytes(2, "big")
            + self.features.to_bytes(4, "big")
        )

    @classmethod
    def decode(cls, raw: bytes) -> Optional["WireHandshake"]:
        if (
            len(raw) != HANDSHAKE_LEN
            or raw[:4] != HANDSHAKE_MAGIC
            or raw[4] != HANDSHAKE_VERSION
        ):
            return None
        return cls(
            wire_version=int.from_bytes(raw[5:7], "big"),
            engine_version=int.from_bytes(raw[7:9], "big"),
            features=int.from_bytes(raw[9:13], "big"),
        )


def node_trace_origin(pub: bytes) -> bytes:
    """8-byte node lane id for the fleet trace (stable per pubkey)."""
    return keccak256(pub)[:8]


def era_trace_id(pub: bytes, era: int) -> bytes:
    """The 8-byte trace id a node attaches to its era-`era` consensus
    traffic. A pure function of (sender, era): every observer derives the
    identical id, so cross-node causality needs no id exchange."""
    return keccak256(pub + write_i64(era))[:8]


@dataclass(frozen=True)
class MessageBatch:
    sender: bytes  # 33-byte compressed ECDSA pubkey
    signature: bytes  # 65-byte recoverable sig over keccak(content)
    content: bytes  # zlib-compressed encoded message list

    def encode(self) -> bytes:
        return (
            write_bytes(self.sender)
            + write_bytes(self.signature)
            + write_bytes(self.content)
        )

    @classmethod
    def decode(cls, data: bytes) -> "MessageBatch":
        r = Reader(data)
        sender = r.bytes_()
        sig = r.bytes_()
        content = r.bytes_()
        r.assert_eof()
        return cls(sender, sig, content)

    def verify(self) -> bool:
        return ecdsa.verify_hash(
            self.sender, keccak256(self.content), self.signature
        )

    def messages(self) -> List[NetworkMessage]:
        # decompress with a hard output cap: zlib.decompress's bufsize is only
        # an initial buffer size, so a small compressed frame could otherwise
        # expand to tens of GB before any size check runs (zip-bomb)
        d = zlib.decompressobj()
        raw = d.decompress(self.content, 1 << 26)
        if d.unconsumed_tail or not d.eof:
            raise ValueError("batch too large")
        # bytes past the zlib stream end land in d.unused_data and are
        # IGNORED here by design: that tail is where the optional trace
        # trailer rides (trace_trailer()), and ignoring unknown tails is
        # what makes the trailer forward-compatible
        r = Reader(raw)
        out = []
        for _ in range(r.u32()):
            out.append(NetworkMessage.decode_from(r))
        r.assert_eof()
        return out

    def trace_trailer(self) -> Optional[Tuple[bytes, int, bytes]]:
        """Parse the optional trace-context trailer: (origin, era,
        trace_id), or None when absent. O(1) — reads the content SUFFIX
        without decompressing, so the receive hot path pays a 5-byte
        compare per batch. A zlib stream coincidentally ending in the
        magic+version bytes (2^-40) would yield a garbage-but-harmless
        trace context; the trailer is observability-only and never feeds
        consensus."""
        c = self.content
        if len(c) < TRACE_TRAILER_LEN:
            return None
        tail = c[len(c) - TRACE_TRAILER_LEN:]
        if (
            tail[:4] != TRACE_TRAILER_MAGIC
            or tail[4] != TRACE_TRAILER_VERSION
        ):
            return None
        origin = tail[5:13]
        era = int.from_bytes(tail[13:21], "big", signed=True)
        return origin, era, tail[21:29]

    def handshake(self) -> Optional[WireHandshake]:
        """Parse the optional version-handshake block, or None when absent.
        O(1) suffix reads, like trace_trailer(): the block sits either at
        the very end of content (no trailer on this batch) or immediately
        before the 29-byte trace trailer."""
        c = self.content
        for off in (len(c) - HANDSHAKE_LEN,
                    len(c) - HANDSHAKE_LEN - TRACE_TRAILER_LEN):
            if off < 0:
                continue
            hs = WireHandshake.decode(c[off:off + HANDSHAKE_LEN])
            if hs is not None:
                return hs
        return None


class MessageFactory:
    """Builds + signs message batches (reference MessageFactory.cs:13-103)."""

    def __init__(self, ecdsa_priv: bytes):
        self._priv = ecdsa_priv
        self.public_key = ecdsa.public_key_bytes(ecdsa_priv)
        # emit the trace-context trailer on consensus-bearing batches.
        # On by default (the trailer is invisible to trailer-free
        # decoders); tests flip it off to model a pre-trailer sender
        self.trace_trailer = True
        self._origin = node_trace_origin(self.public_key)
        # version handshake: advertised on every batch. Tests and the
        # rolling-upgrade drill flip `handshake` off (or the versions
        # down) to model a legacy / mid-upgrade sender
        self.handshake = True
        self.wire_version = WIRE_VERSION
        self.engine_version = ENGINE_VERSION
        self.features = FEATURES_DEFAULT

    def batch(self, msgs: List[NetworkMessage]) -> MessageBatch:
        raw = write_u32(len(msgs)) + b"".join(m.encode() for m in msgs)
        content = zlib.compress(raw, level=1)
        if self.handshake:
            # before the trace trailer: the trailer must stay the
            # outermost suffix (see tail layout at HANDSHAKE_MAGIC)
            content += WireHandshake(
                wire_version=self.wire_version,
                engine_version=self.engine_version,
                features=self.features,
            ).encode()
        if self.trace_trailer:
            # era = the newest era among the batch's consensus messages
            # (a flush batch can mix eras under pipelining; the receiver's
            # per-era set keeps ids for every era it actually dispatches)
            era = None
            for m in msgs:
                if m.kind == KIND_CONSENSUS and len(m.body) >= 8:
                    e = int.from_bytes(m.body[:8], "big", signed=True)
                    if era is None or e > era:
                        era = e
            if era is not None:
                content += (
                    TRACE_TRAILER_MAGIC
                    + bytes([TRACE_TRAILER_VERSION])
                    + self._origin
                    + write_i64(era)
                    + era_trace_id(self.public_key, era)
                )
        with tracing.account("frame_sign"):  # one keccak, one ECDSA signature
            sig = ecdsa.sign_hash(self._priv, keccak256(content))
        return MessageBatch(
            sender=self.public_key, signature=sig, content=content
        )


# -- fast state sync (reference FastSynchronizerBatch / StateDownloader) -----


def fast_sync_request(height: int) -> NetworkMessage:
    """Ask for the block + state roots at `height` (0 = serving peer's tip)."""
    return NetworkMessage(KIND_FAST_SYNC_REQUEST, write_u64(height))


def parse_fast_sync_request(msg: NetworkMessage) -> int:
    return Reader(msg.body).u64()


def fast_sync_reply(block: Optional[Block], roots_enc: bytes) -> NetworkMessage:
    body = write_bytes(block.encode() if block else b"") + write_bytes(roots_enc)
    return NetworkMessage(KIND_FAST_SYNC_REPLY, body)


def parse_fast_sync_reply(msg: NetworkMessage):
    r = Reader(msg.body)
    raw = r.bytes_()
    block = Block.decode(raw) if raw else None
    return block, r.bytes_()


def trie_nodes_request(hashes: List[bytes]) -> NetworkMessage:
    return NetworkMessage(KIND_TRIE_NODES_REQUEST, write_bytes_list(hashes))


def parse_trie_nodes_request(msg: NetworkMessage) -> List[bytes]:
    return Reader(msg.body).bytes_list()


def trie_nodes_reply(nodes: List[bytes]) -> NetworkMessage:
    """Node encodings only: receivers verify content-addressing
    (keccak(node) must equal the requested hash), so replies are
    trustless."""
    return NetworkMessage(KIND_TRIE_NODES_REPLY, write_bytes_list(nodes))


def parse_trie_nodes_reply(msg: NetworkMessage) -> List[bytes]:
    return Reader(msg.body).bytes_list()


def trie_nodes_request_id(request_id: int, hashes: List[bytes]) -> NetworkMessage:
    """Request-id variant: the reply echoes `request_id`, so a late or
    duplicated reply to an abandoned batch is simply dropped by the
    scheduler instead of being consumed as the current batch's answer."""
    return NetworkMessage(
        KIND_TRIE_NODES_REQUEST_ID,
        write_u64(request_id) + write_bytes_list(hashes),
    )


def parse_trie_nodes_request_id(msg: NetworkMessage) -> Tuple[int, List[bytes]]:
    r = Reader(msg.body)
    rid = r.u64()
    hashes = r.bytes_list()
    r.assert_eof()
    return rid, hashes


def trie_nodes_reply_id(request_id: int, nodes: List[bytes]) -> NetworkMessage:
    return NetworkMessage(
        KIND_TRIE_NODES_REPLY_ID,
        write_u64(request_id) + write_bytes_list(nodes),
    )


def parse_trie_nodes_reply_id(msg: NetworkMessage) -> Tuple[int, List[bytes]]:
    r = Reader(msg.body)
    rid = r.u64()
    nodes = r.bytes_list()
    r.assert_eof()
    return rid, nodes


def snapshot_request(request_id: int, cursor: bytes, limit: int) -> NetworkMessage:
    """Ask for one page of the peer's trie-node rows starting AFTER
    `cursor` (b"" = from the beginning), at most `limit` records. The
    cursor is a plain trie-node hash, so a partially shipped snapshot
    resumes from any other peer."""
    return NetworkMessage(
        KIND_SNAPSHOT_REQUEST,
        write_u64(request_id) + write_bytes(cursor) + write_u32(limit),
    )


def parse_snapshot_request(msg: NetworkMessage) -> Tuple[int, bytes, int]:
    r = Reader(msg.body)
    rid = r.u64()
    cursor = r.bytes_()
    limit = r.u32()
    r.assert_eof()
    return rid, cursor, limit


def snapshot_reply(
    request_id: int, next_cursor: bytes, done: bool, records: List[bytes]
) -> NetworkMessage:
    """One page of raw trie-node encodings. Records are self-certifying:
    the importer stores each under keccak(record), so a bogus record can
    waste bandwidth but never poison state (the root walk won't reach it)."""
    body = (
        write_u64(request_id)
        + write_bytes(next_cursor)
        + bytes([1 if done else 0])
        + write_bytes_list(records)
    )
    return NetworkMessage(KIND_SNAPSHOT_REPLY, body)


def parse_snapshot_reply(msg: NetworkMessage) -> Tuple[int, bytes, bool, List[bytes]]:
    r = Reader(msg.body)
    rid = r.u64()
    next_cursor = r.bytes_()
    done = r.raw(1)[0] != 0
    records = r.bytes_list()
    r.assert_eof()
    return rid, next_cursor, done, records


# -- peer discovery (gossip-learned addresses; reference: the hub relay
# network's bootstrap + peer exchange, HubConnector.cs:26-105 +
# config_mainnet.json:22-33 — here peers exchange dialable addresses
# directly) ------------------------------------------------------------------


def peers_request(my_host: str, my_port: int) -> NetworkMessage:
    """Ask a peer for its address book; carries OUR listening address so an
    inbound-only acquaintance becomes dialable."""
    return NetworkMessage(
        KIND_PEERS_REQUEST,
        write_bytes(my_host.encode()) + write_u32(my_port),
    )


def parse_peers_request(msg: NetworkMessage) -> Tuple[str, int]:
    r = Reader(msg.body)
    host = r.bytes_().decode()
    port = r.u32()
    r.assert_eof()
    return host, port


def relay_register() -> NetworkMessage:
    """Sent by a NAT'd node to its relay: hold my registration and deliver
    relay_forward traffic addressed to me over this connection. Re-sent
    periodically (refreshes the TTL and keeps the NAT mapping alive)."""
    return NetworkMessage(KIND_RELAY_REGISTER, b"")


def relay_forward(target_pub: bytes, inner_batch: bytes) -> NetworkMessage:
    """Wrap a SIGNED batch for `target_pub` to be delivered by the relay.
    The inner batch carries the original sender's signature, so the relay
    cannot forge or tamper — it only moves bytes."""
    return NetworkMessage(
        KIND_RELAY_FORWARD, write_bytes(target_pub) + write_bytes(inner_batch)
    )


def parse_relay_forward(msg: NetworkMessage) -> Tuple[bytes, bytes]:
    r = Reader(msg.body)
    target = r.bytes_()
    inner = r.bytes_()
    r.assert_eof()
    return target, inner


# host sentinel in peers books for a peer reachable only through a relay:
# "~" + relay pubkey hex (port is ignored)
RELAY_HOST_PREFIX = "~"


def relay_host(relay_pub: bytes) -> str:
    return RELAY_HOST_PREFIX + relay_pub.hex()


def parse_relay_host(host: str):
    """The relay pubkey from a sentinel host, or None for a normal host."""
    if not host.startswith(RELAY_HOST_PREFIX):
        return None
    try:
        pub = bytes.fromhex(host[1:])
    except ValueError:
        return None
    return pub if len(pub) == 33 else None


def peers_reply(peers: List[Tuple[bytes, str, int]]) -> NetworkMessage:
    body = write_u32(len(peers))
    for pub, host, port in peers:
        body += write_bytes(pub) + write_bytes(host.encode()) + write_u32(port)
    return NetworkMessage(KIND_PEERS_REPLY, body)


def parse_peers_reply(msg: NetworkMessage) -> List[Tuple[bytes, str, int]]:
    r = Reader(msg.body)
    out = []
    for _ in range(r.u32()):
        pub = r.bytes_()
        host = r.bytes_().decode()
        port = r.u32()
        if len(pub) != 33:
            raise ValueError("bad peer pubkey length")
        out.append((pub, host, port))
    r.assert_eof()
    return out
