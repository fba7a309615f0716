"""ctypes binding for the native consensus engine (libconsensus_rt).

`NativeSimulatedNetwork` is a drop-in for `simulator.SimulatedNetwork`: the
delivery queue and ALL seven consensus protocols run inside the C++ engine
(native/consensus_rt.cpp). The flood protocols (BinaryBroadcast,
BinaryAgreement, ReliableBroadcast, CommonSubset) are hosted wholesale; the
crypto-bearing protocols (CommonCoin, HoneyBadger, RootProtocol) are split —
the engine owns their MESSAGE state machines while Python host shims
(native_hosts.py) own every cryptographic operation, reached through BATCHED
boundary crossings instead of one Python round-trip per message. The Python
protocol classes remain the pinned cryptographic oracle: a TAKE_FIRST run is
bit-identical across engines (tests/test_native_rt.py).

A validator whose `_extra_factories` overrides one of the crypto protocols
(the malicious-subclass test pattern, or forcing the Python engines for
debugging) keeps that protocol in Python: its ownership bit stays clear and
its opaque messages keep flowing through the legacy per-message callback.

Reference roles covered: AbstractProtocol's thread+queue runtime
(/root/reference/src/Lachain.Consensus/AbstractProtocol.cs:11-168) and the
test DeliveryService (test/Lachain.ConsensusTest/DeliverySerivce.cs:10-124).
"""
from __future__ import annotations

import ctypes
import os
import struct
import weakref
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

from ..utils import metrics, tracing
from ..utils.native_build import ensure_built
from . import messages as M
from .era import EraRouter
from .keys import PrivateConsensusKeys, PublicConsensusKeys
from .native_hosts import (
    RQ_COIN,
    RQ_HB,
    RQ_ROOT,
    XO_COIN_COMBINE,
    XO_COIN_RESULT,
    XO_COIN_SIGN,
    XO_EVIDENCE,
    XO_HB_ACS,
    XO_HB_DONE,
    XO_HB_QUEUE,
    XO_NAMES,
    XO_RBC_ENCODE,
    XO_RBC_NEED,
    XO_ROOT_INPUT,
    XO_ROOT_PRODUCE,
    XO_ROOT_SIGN,
    XO_ROOT_VERIFY,
    CoinHost,
    HoneyBadgerHost,
    RbcHost,
    RootHost,
)
from .simulator import DeliveryMode

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "native")

# opaque payload kinds (shared contract with consensus_rt.cpp MT_OPAQUE)
KIND_DECRYPTED = 0
KIND_SIGNED_HEADER = 1
KIND_COIN = 2

# per-validator native-ownership mask (consensus_rt.cpp enum OwnMask)
OWN_HB = 1
OWN_COIN = 2
OWN_ROOT = 4

# engine message types (consensus_rt.cpp enum MsgType)
MT_BVAL, MT_AUX, MT_CONF, MT_VAL, MT_ECHO, MT_READY, MT_OPAQUE = range(7)

# -- the seam to validators hosted outside the engine -------------------------
# One record form both ways (consensus_rt.cpp Engine::out_record/inject):
# be32 sender | be32 target (-1: every remote validator) | be32 era |
# u8 type | be32 agreement | be32 epoch | u8 value | u8 opq_kind |
# be32 shard_index | be32 len + root | be32 nbranch + (be32 len + hash)* |
# be32 len + data
_SEAM_HEAD = struct.Struct(">iiiBiiBBiI")


class SeamRecord(NamedTuple):
    sender: int
    target: int  # -1: a broadcast
    era: int
    type: int  # MT_*
    agreement: int  # BB/coin: agreement; VAL/ECHO/READY: the RBC slot
    epoch: int
    value: int  # BVAL/AUX: the bit; CONF: the 2-bit set
    opq_kind: int  # MT_OPAQUE: KIND_*
    shard_index: int
    root: bytes
    branch: Tuple[bytes, ...]
    data: bytes


def encode_seam_record(
    sender: int,
    target: int,
    type: int,
    agreement: int = 0,
    epoch: int = 0,
    value: int = 0,
    opq_kind: int = 0,
    shard_index: int = 0,
    root: bytes = b"",
    branch: Sequence[bytes] = (),
    data: bytes = b"",
    era: int = 0,
) -> bytes:
    parts = [
        _SEAM_HEAD.pack(
            sender, target, era, type, agreement, epoch, value, opq_kind,
            shard_index, len(root),
        ),
        root,
        len(branch).to_bytes(4, "big"),
    ]
    for h in branch:
        parts += [len(h).to_bytes(4, "big"), h]
    parts += [len(data).to_bytes(4, "big"), data]
    return b"".join(parts)


def decode_seam_records(blob: bytes) -> List[SeamRecord]:
    out = []
    off, end = 0, len(blob)
    while off < end:
        head = _SEAM_HEAD.unpack_from(blob, off)
        off += _SEAM_HEAD.size
        root = blob[off : off + head[9]]
        off += head[9]
        nbranch = int.from_bytes(blob[off : off + 4], "big")
        off += 4
        branch = []
        for _ in range(nbranch):
            ln = int.from_bytes(blob[off : off + 4], "big")
            branch.append(blob[off + 4 : off + 4 + ln])
            off += 4 + ln
        ln = int.from_bytes(blob[off : off + 4], "big")
        data = blob[off + 4 : off + 4 + ln]
        off += 4 + ln
        out.append(SeamRecord(*head[:9], root, tuple(branch), data))
    return out

# labeled counter of every engine->Python boundary crossing; op
# "opaque_message" is the legacy per-message callback the batched ops replace
CROSSINGS_METRIC = "consensus_callback_crossings_total"
# the engine's exclusive per-message dispatch time by protocol family
# (TP_NAMES): callbacks into Python subtracted, no interval to put a span on
DISPATCH_METRIC = tracing.DISPATCH_METRIC  # one name, two engines write it

_OPAQUE_CB = ctypes.CFUNCTYPE(
    None,
    ctypes.c_int32,  # target
    ctypes.c_int32,  # sender
    ctypes.c_int32,  # era
    ctypes.c_int32,  # kind
    ctypes.c_int32,  # agreement
    ctypes.c_int32,  # epoch
    ctypes.POINTER(ctypes.c_uint8),
    ctypes.c_size_t,
)
_ACS_CB = ctypes.CFUNCTYPE(
    None,
    ctypes.c_int32,  # target
    ctypes.c_int32,  # era
    ctypes.c_int32,  # nslots
    ctypes.POINTER(ctypes.c_int32),
    ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
    ctypes.POINTER(ctypes.c_size_t),
)
_COINREQ_CB = ctypes.CFUNCTYPE(
    None, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32
)
_CROSS_CB = ctypes.CFUNCTYPE(
    None,
    ctypes.c_int32,  # target
    ctypes.c_int32,  # era
    ctypes.c_int32,  # op (XO_*)
    ctypes.c_int32,  # a
    ctypes.c_int32,  # b
    ctypes.POINTER(ctypes.c_uint8),
    ctypes.c_size_t,
)

_lib_cache: List[Any] = [None]


def load_rt():
    if _lib_cache[0] is not None:
        return _lib_cache[0]
    # LACHAIN_CONSENSUS_LIB loads an alternate engine build verbatim (the
    # ASan/TSan gates in tests/native/ point it at instrumented builds) —
    # no rebuild, same contract as LACHAIN_LSM_LIB in storage/lsm.py
    lib_path = os.environ.get("LACHAIN_CONSENSUS_LIB") or ensure_built(
        _NATIVE_DIR, "libconsensus_rt.so"
    )
    lib = ctypes.CDLL(lib_path)
    lib.lt_crt_version.restype = ctypes.c_int
    _crt_ver = lib.lt_crt_version()
    assert _crt_ver in (6, 7, 8), _crt_ver
    lib.rt_new.restype = ctypes.c_void_p
    lib.rt_new.argtypes = [
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_uint32,
        ctypes.c_uint64,
        ctypes.c_int,
    ]
    lib.rt_free.argtypes = [ctypes.c_void_p]
    lib.rt_set_callbacks.argtypes = [
        ctypes.c_void_p,
        _OPAQUE_CB,
        _ACS_CB,
        _COINREQ_CB,
        _CROSS_CB,
    ]
    lib.rt_set_owned.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
    lib.rt_set_coin_need.argtypes = [ctypes.c_void_p, ctypes.c_int]
    # version 7 added the batched RBC boundary (XO_RBC_ENCODE/NEED). Probe it
    # so a stale .so built from older sources degrades to the engine's
    # per-message RBC path instead of crashing (keccak_batch-style fallback).
    lib._lt_has_rbc_host = _crt_ver >= 7 and hasattr(lib, "rt_set_rbc_host")
    if lib._lt_has_rbc_host:
        lib.rt_set_rbc_host.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.rt_request.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
    ]
    lib.rt_post.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.rt_hb_ready_export.restype = ctypes.c_size_t
    lib.rt_hb_ready_export.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.rt_native_handled.restype = ctypes.c_uint64
    lib.rt_native_handled.argtypes = [ctypes.c_void_p]
    lib.rt_debug_state.restype = ctypes.c_size_t
    lib.rt_debug_state.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.rt_mute.argtypes = [ctypes.c_void_p, ctypes.c_int]
    # version 8: the seam to validators hosted outside the engine
    lib._lt_has_seam = _crt_ver >= 8
    if lib._lt_has_seam:
        lib.rt_set_remote.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.rt_out_drain.restype = ctypes.c_size_t
        lib.rt_out_drain.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_size_t,
        ]
        lib.rt_inject.restype = ctypes.c_size_t
        lib.rt_inject.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
    lib.rt_advance_era.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
    lib.rt_post_acs_input.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.rt_post_coin_result.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
    ]
    lib.rt_broadcast_opaque.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.rt_send_opaque.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.rt_run.restype = ctypes.c_size_t
    lib.rt_run.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    lib.rt_request_stop.argtypes = [ctypes.c_void_p]
    lib.rt_opaque_pending.restype = ctypes.c_uint64
    lib.rt_opaque_pending.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.rt_queue_len.restype = ctypes.c_size_t
    lib.rt_queue_len.argtypes = [ctypes.c_void_p]
    lib.rt_delivered.restype = ctypes.c_uint64
    lib.rt_delivered.argtypes = [ctypes.c_void_p]
    lib.rt_monotonic_ns.restype = ctypes.c_uint64
    lib.rt_monotonic_ns.argtypes = []
    lib.rt_trace_configure.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    lib.rt_trace_dropped.restype = ctypes.c_uint64
    lib.rt_trace_dropped.argtypes = [ctypes.c_void_p]
    lib.rt_phase_totals.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.rt_trace_drain.restype = ctypes.c_size_t
    lib.rt_trace_drain.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_size_t,
    ]
    _lib_cache[0] = lib
    return lib


# -- flight recorder ---------------------------------------------------------

# consensus_rt.cpp trace record contract: 32-byte big-endian records
_TRACE_RECORD = struct.Struct(">QQIIII")
# (kinds 2 and 5 are reserved: a callback's interval is the span cross.<op>,
# dispatch seconds by family are DISPATCH_METRIC; old buffers held both)
TK_ERA_ADVANCE, TK_POST, TK_STAGE, TK_WAIT = 1, 3, 4, 6
# TP_* dispatch-phase buckets -> DISPATCH_METRIC's family label
TP_NAMES = {1: "rbc", 2: "ba", 3: "coin", 4: "tpke", 5: "commit", 6: "other"}
# WR_* wait resources (TK_WAIT.a) -> era-report wait buckets (tracing.WAIT_RESOURCES)
WR_NAMES = {1: "net", 2: "crypto_flush", 3: "device", 4: "fsync", 5: "sched"}
# the coarse PO_* ops the engine records (native_post keeps per-slot ops out)
_PO_TRACE_NAMES = {2: "coin_result", 3: "hb_acs_input", 5: "hb_acs_done",
                   12: "root_header"}
_TS_NAMES = {1: "acs_result"}


# clock-offset handshake shared with the LSM binding
clock_offset = tracing.clock_offset


def decode_consensus_trace(raw: bytes, offset: float) -> List[dict]:
    """Raw drain buffer -> merged-tracer event dicts (see
    tracing.register_native_source for the schema)."""
    evs: List[dict] = []
    for i in range(0, len(raw) - (len(raw) % 32), 32):
        ts, dur, kind, tid, a, b = _TRACE_RECORD.unpack_from(raw, i)
        start = ts / 1e9 + offset
        end = (ts + dur) / 1e9 + offset
        common = dict(
            start=start,
            end=end,
            pid=tracing.NATIVE_CONSENSUS_PID,
            pname="native-consensus",
        )
        if kind == TK_ERA_ADVANCE:
            evs.append(
                dict(
                    common,
                    name="era_advance",
                    cat="native.consensus",
                    tid=tid,
                    tname=f"validator-{tid}",
                    args={"vid": tid, "new_era": a, "old_era": b},
                )
            )
        elif kind == TK_POST:
            op = _PO_TRACE_NAMES.get(a, str(a))
            evs.append(
                dict(
                    common,
                    name=f"post:{op}",
                    cat="native.consensus",
                    tid=tid,
                    tname=f"validator-{tid}",
                    args={"op": op, "era": b, "vid": tid},
                )
            )
        elif kind == TK_STAGE:
            evs.append(
                dict(
                    common,
                    name=f"stage:{_TS_NAMES.get(a, str(a))}",
                    cat="native.consensus",
                    tid=tid,
                    tname=f"validator-{tid}",
                    args={"stage": a, "era": b, "vid": tid},
                )
            )
        elif kind == TK_WAIT:
            res = WR_NAMES.get(a, str(a))
            evs.append(
                dict(
                    common,
                    name=f"wait:{res}",
                    cat="native.wait",
                    tid=0,
                    tname="dispatch",
                    args={"resource": res, "era": b},
                )
            )
            metrics.observe_hist(
                "wait_seconds", dur / 1e9, labels={"resource": res}
            )
    return evs


def _cross_begin(op: str, era: int, vid: int) -> int:
    """One engine->Python callback: counted under CROSSINGS_METRIC and
    opened as the span `cross.<op>`, which its caller ends."""
    metrics.inc(CROSSINGS_METRIC, labels={"op": op})
    if not tracing.capacity():
        return 0
    return tracing.begin("cross." + op, "engine", era=era, vid=vid)


@dataclass(frozen=True)
class NativeCoinParent:
    """Result address for a PYTHON CommonCoin requested by a native
    BinaryAgreement (the coin ownership bit is clear — override factory):
    the Python coin's emit_result routes back into the engine."""

    agreement: int
    epoch: int
    era: int = 0  # routes the result to the right per-era engine


class _EraHosts:
    """Per-era container for the native-protocol host shims of one router."""

    __slots__ = ("coins", "hb", "root", "rbc", "py_parents")

    def __init__(self):
        self.coins: Dict[tuple, CoinHost] = {}
        self.hb: Optional[HoneyBadgerHost] = None
        self.root: Optional[RootHost] = None
        self.rbc: Optional[RbcHost] = None
        # parent protocol ids of PYTHON protocols awaiting a native result
        self.py_parents: Dict[Any, Any] = {}


class NativeEraRouter(EraRouter):
    """EraRouter whose protocols live in the native engine.

    Flood protocols are engine-only. Crypto-bearing protocols are
    engine-hosted with Python crypto shims (native_hosts.py) unless an
    `_extra_factories` override forces the Python class — then requests and
    messages route exactly as in EraRouter, crossing the engine as opaque
    payloads via the legacy per-message callbacks.
    """

    def __init__(
        self,
        era: int,
        my_id: int,
        public_keys: PublicConsensusKeys,
        private_keys: PrivateConsensusKeys,
        net: "NativeSimulatedNetwork",
        extra_factories=None,
        journal=None,
        evidence=None,
    ):
        def _no_send(target, payload):  # pragma: no cover
            raise RuntimeError("native router transports via the engine")

        super().__init__(
            era,
            my_id,
            public_keys,
            private_keys,
            send=_no_send,
            extra_factories=extra_factories,
            journal=journal,
            evidence=evidence,
        )
        self._net = net
        self._acs_parent: Any = None
        self.crypto_batcher = None  # set by the network when batching is on
        self.rbc_batcher = None  # set by the network when RBC batching is on
        self._root_ctx = None  # (producer, ecdsa_priv, ecdsa_pubs)
        self._era_hosts: Dict[int, _EraHosts] = {}
        self._native_results: Dict[Any, Any] = {}

    # -- native ownership ------------------------------------------------------
    def _native_mask(self) -> int:
        """Which crypto protocols THIS validator hosts natively. Computed
        lazily (tests install override factories after construction) and
        synced to the engine before any request enters it."""
        mask = 0
        if M.CoinId not in self._extra_factories:
            mask |= OWN_COIN
        if (
            M.HoneyBadgerId not in self._extra_factories
            and self.crypto_batcher is not None
            and self._net._era_fn_available()
        ):
            mask |= OWN_HB
        # native Root drives native HB + the native nonce coin; a validator
        # running either of those in Python must run Root in Python too
        if (
            self._root_ctx is not None
            and M.RootProtocolId not in self._extra_factories
            and (mask & OWN_HB)
            and (mask & OWN_COIN)
        ):
            mask |= OWN_ROOT
        return mask

    # -- host shims ------------------------------------------------------------
    def _hosts(self, era: int) -> _EraHosts:
        hs = self._era_hosts.get(era)
        if hs is None:
            hs = self._era_hosts[era] = _EraHosts()
        return hs

    def hb_host(self, era: int) -> HoneyBadgerHost:
        hs = self._hosts(era)
        if hs.hb is None:
            hs.hb = HoneyBadgerHost(self, era)
        return hs.hb

    def coin_host(self, era: int, agreement: int, epoch: int) -> CoinHost:
        hs = self._hosts(era)
        key = (agreement, epoch)
        host = hs.coins.get(key)
        if host is None:
            cid = M.CoinId(era=era, agreement=agreement, epoch=epoch)
            host = hs.coins[key] = CoinHost(self, cid)
        return host

    def rbc_host(self, era: int) -> RbcHost:
        hs = self._hosts(era)
        if hs.rbc is None:
            hs.rbc = RbcHost(self, era)
        return hs.rbc

    def root_host(self, era: int) -> RootHost:
        hs = self._hosts(era)
        if hs.root is None:
            producer, priv, pubs = self._root_ctx
            hs.root = RootHost(self, era, producer, priv, pubs)
        return hs.root

    def _native_send(self, payload):
        """Journal-aware emission half of EraRouter.broadcast for payloads
        whose message state machine lives in the engine: durable-record
        (possibly substituting previously recorded wire bytes — the
        no-self-equivocation latch) + outbox, WITHOUT the transport send; the
        caller hands the returned wire payload to the engine, which owns
        delivery."""
        payload = self._durable_send(None, payload)
        self._record_outbox(None, payload)
        return payload

    # -- outbound: divert into the engine -------------------------------------
    def internal_request(self, req: M.Request) -> None:
        to = req.to_id
        if isinstance(to, M.CommonSubsetId):
            self._acs_parent = req.from_id
            self._net._post_acs_input(self._my_id, req.input, era=to.era)
            return
        if isinstance(
            to,
            (M.BinaryAgreementId, M.BinaryBroadcastId, M.ReliableBroadcastId),
        ):
            raise RuntimeError(f"natively-owned protocol requested: {to}")
        to_era = getattr(to, "era", None)
        if to_era is not None and self.window_floor <= to_era <= self.era:
            mask = self._native_mask()
            if isinstance(to, M.RootProtocolId) and (mask & OWN_ROOT):
                self._net._sync_owner(self._my_id)
                self._net._rt_request(self._my_id, RQ_ROOT, 0, 0, era=to_era)
                return
            if isinstance(to, M.HoneyBadgerId) and (mask & OWN_HB):
                self._net._sync_owner(self._my_id)
                self._hosts(to.era).py_parents["hb"] = req.from_id
                self._net._rt_request(self._my_id, RQ_HB, 0, 0, era=to_era)
                if to in self._native_results:
                    return  # done-replay: the result was re-routed already
                self.hb_host(to.era).handle_input(req.input)
                return
            if isinstance(to, M.CoinId) and (mask & OWN_COIN):
                self._net._sync_owner(self._my_id)
                self._hosts(to.era).py_parents[
                    ("coin", to.agreement, to.epoch)
                ] = req.from_id
                self._net._rt_request(
                    self._my_id, RQ_COIN, to.agreement, to.epoch, era=to_era
                )
                return
        super().internal_request(req)

    def internal_response(self, res: M.Result) -> None:
        if isinstance(res.to_id, NativeCoinParent):
            self._net._post_coin_result(
                self._my_id,
                res.to_id.agreement,
                res.to_id.epoch,
                res.value,
                era=res.to_id.era,
            )
            return
        if res.to_id is None:
            # top-level protocol completed (e.g. Root produced its block):
            # break the engine out of its chunk so the driver can re-check
            # done() promptly — mirrors the Python simulator's per-message
            # done() check and keeps lag-round coin work off the hot path
            self._net._request_stop(era=getattr(res.from_id, "era", None))
            return
        super().internal_response(res)

    def broadcast(self, payload) -> None:
        # python-side protocol emission: durable-record + outbox exactly as
        # EraRouter.broadcast, then transport through the engine
        payload = self._native_send(payload)
        self._engine_transport(payload)

    def _engine_transport(self, payload) -> None:
        """Hand one host-shim payload to the engine for delivery (the
        transport half of broadcast — no journaling, no outbox record)."""
        if isinstance(payload, M.DecryptedMessage):
            self._net._bcast_opaque(
                self._my_id,
                KIND_DECRYPTED,
                payload.share_id,
                0,
                payload.payload,
                era=payload.hb.era,
            )
        elif isinstance(payload, M.SignedHeaderMessage):
            data = (
                len(payload.header_bytes).to_bytes(4, "big")
                + payload.header_bytes
                + payload.signature
            )
            self._net._bcast_opaque(
                self._my_id, KIND_SIGNED_HEADER, 0, 0, data, era=payload.root.era
            )
        elif isinstance(payload, M.CoinMessage):
            self._net._bcast_opaque(
                self._my_id,
                KIND_COIN,
                payload.coin.agreement,
                payload.coin.epoch,
                payload.share,
                era=payload.coin.era,
            )
        else:
            raise TypeError(f"unexpected python-protocol payload {type(payload)}")

    def replay_outbox(
        self, era: int, requester: int, limit: Optional[int] = None
    ) -> int:
        """Retransmission service over the engine transport. The engine only
        floods (its receive paths are idempotent — repeated shares are
        dropped by the per-sender latches), so a targeted replay request is
        answered with a re-broadcast of the recorded payloads. The engine
        runs the router's current era only; older eras' flood traffic is
        engine-internal and already superseded by the decided block.
        `limit` caps the batch, same contract as EraRouter.replay_outbox."""
        if not (self.window_floor <= era <= self.era):
            return 0
        payloads = self.outbox_payloads(era, requester)
        if limit is not None:
            payloads = payloads[:limit]
        for payload in payloads:
            self._engine_transport(payload)
        if payloads:
            from ..utils import metrics

            metrics.inc("consensus_outbox_replayed_total", len(payloads))
        return len(payloads)

    def send_to(self, validator: int, payload) -> None:
        raise TypeError("python-side protocols only broadcast")

    def _create(self, pid):
        if isinstance(
            pid,
            (
                M.BinaryBroadcastId,
                M.BinaryAgreementId,
                M.ReliableBroadcastId,
                M.CommonSubsetId,
            ),
        ):
            raise RuntimeError(f"natively-owned protocol id {pid}")
        if (
            isinstance(pid, M.RootProtocolId)
            and type(pid) not in self._extra_factories
            and self._root_ctx is not None
        ):
            # Root context was given natively (set_root_context) but this
            # validator cannot own Root (an HB/Coin override forced Python):
            # fall back to the Python RootProtocol built from the same context
            from .root_protocol import RootProtocol

            producer, priv, pubs = self._root_ctx
            return RootProtocol(
                pid, self, producer=producer, ecdsa_priv=priv, ecdsa_pubs=pubs
            )
        return super()._create(pid)

    def result_of(self, pid) -> Any:
        if pid in self._native_results:
            return self._native_results[pid]
        return super().result_of(pid)

    def coin_values(self, era: int) -> Dict[Tuple[int, int], bool]:
        """(agreement, epoch) -> value of every coin this validator combined
        in `era`; the hosts are kept until the era after next begins."""
        hs = self._era_hosts.get(era)
        return {
            key: host._signer.signature.parity
            for key, host in (hs.coins.items() if hs else ())
            if host._signer.signature is not None
        }

    def native_state(self) -> str:
        """Engine-side state of this validator's natively-owned protocols
        (for watchdog stall reports)."""
        return self._net.native_state_of(self._my_id)

    def advance_era(self, new_era: int) -> None:
        if new_era <= self.era:
            return
        old_era = self.era
        super().advance_era(new_era)
        # host shims and native results follow the same retention as
        # protocol instances: keep the last active era, drop older
        cutoff = min(new_era - 1, old_era)
        self._prune_native_state(cutoff)
        self._net._advance_era(self._my_id, new_era)

    def commit_era_gc(self, committed_era: int) -> None:
        super().commit_era_gc(committed_era)
        self._prune_native_state(
            committed_era + 1 - max(self.pipeline_window, 1)
        )

    def _prune_native_state(self, cutoff: int) -> None:
        for e in [e for e in self._era_hosts if e < cutoff]:
            del self._era_hosts[e]
        for pid in [
            p
            for p in self._native_results
            if getattr(p, "era", cutoff) < cutoff
        ]:
            del self._native_results[pid]

    # -- engine callbacks (legacy per-message path) ----------------------------
    def _on_opaque(
        self, sender: int, era: int, kind: int, agreement: int, epoch: int, data: bytes
    ) -> None:
        if kind == KIND_DECRYPTED:
            payload = M.DecryptedMessage(
                hb=M.HoneyBadgerId(era=era), share_id=agreement, payload=data
            )
        elif kind == KIND_SIGNED_HEADER:
            hlen = int.from_bytes(data[:4], "big")
            payload = M.SignedHeaderMessage(
                root=M.RootProtocolId(era=era),
                header_bytes=data[4 : 4 + hlen],
                signature=data[4 + hlen :],
            )
        elif kind == KIND_COIN:
            payload = M.CoinMessage(
                coin=M.CoinId(era=era, agreement=agreement, epoch=epoch),
                share=data,
            )
        else:  # unknown kind: drop (forward-compat)
            return
        self.dispatch_external(sender, payload)

    def _on_acs_result(self, era: int, result: Dict[int, bytes]) -> None:
        self.internal_response(
            M.Result(
                from_id=M.CommonSubsetId(era=era),
                to_id=self._acs_parent,
                value=result,
            )
        )

    def _on_coin_request(self, era: int, agreement: int, epoch: int) -> None:
        cid = M.CoinId(era=era, agreement=agreement, epoch=epoch)
        super().internal_request(
            M.Request(
                from_id=NativeCoinParent(
                    agreement=agreement, epoch=epoch, era=era
                ),
                to_id=cid,
                input=None,
            )
        )

    # -- engine callbacks (batched crossing path) ------------------------------
    def _on_cross(self, era: int, op: int, a: int, b: int, blob: bytes) -> None:
        if op == XO_COIN_SIGN:
            self.coin_host(era, a, b).sign()
        elif op == XO_COIN_COMBINE:
            self.coin_host(era, a, b).combine(blob)
        elif op == XO_COIN_RESULT:
            # native coin completed for a PYTHON parent (or a direct request)
            value = bool(blob[0]) if blob else False
            cid = M.CoinId(era=era, agreement=a, epoch=b)
            self._native_results[cid] = value
            parent = self._hosts(era).py_parents.pop(("coin", a, b), None)
            if parent is None:
                self._net._request_stop()
            else:
                super().internal_response(
                    M.Result(from_id=cid, to_id=parent, value=value)
                )
        elif op == XO_HB_ACS:
            self.hb_host(era).on_acs(blob)
        elif op == XO_HB_QUEUE:
            self.hb_host(era).on_queue()
        elif op == XO_HB_DONE:
            result = self.hb_host(era).finish()
            hbid = M.HoneyBadgerId(era=era)
            self._native_results[hbid] = result
            if a:  # parent is Python-side (or a direct top-level request)
                parent = self._hosts(era).py_parents.pop("hb", None)
                if parent is None:
                    self._net._request_stop()
                else:
                    super().internal_response(
                        M.Result(from_id=hbid, to_id=parent, value=result)
                    )
        elif op == XO_RBC_ENCODE:
            self.rbc_host(era).on_encode(a, blob)
        elif op == XO_RBC_NEED:
            self.rbc_host(era).on_need(a, blob)
        elif op == XO_ROOT_INPUT:
            self.root_host(era).on_input()
        elif op == XO_ROOT_SIGN:
            # pipelined window: the sign point is the front/tail boundary —
            # the scheduler stashes the coin parity here and resumes the
            # sign on the tail lane once the parent block has committed
            if self._net._defer_sign(self._my_id, era, a):
                return
            self.root_host(era).on_sign(a)
        elif op == XO_ROOT_VERIFY:
            self.root_host(era).on_verify(blob)
        elif op == XO_ROOT_PRODUCE:
            self.root_host(era).on_produce()
        elif op == XO_EVIDENCE:
            # engine equivocation latch tripped: a=offender b=opq_kind,
            # blob = be32(agreement) + be32(epoch). Build the exact record
            # era.py::_latch_first_seen would (evidence-set identity between
            # engines is pinned by tests)
            agreement = int.from_bytes(blob[0:4], "big", signed=True)
            epoch = int.from_bytes(blob[4:8], "big", signed=True)
            if b == KIND_DECRYPTED:
                proto, index = "dec", (agreement,)
            elif b == KIND_COIN:
                proto, index = "coin", (agreement, epoch)
            else:
                proto, index = "hdr", ()
            self.evidence.record_equivocation(era, a, proto, index)
        else:  # unknown op: refuse loudly — a silent drop would stall
            raise RuntimeError(f"unknown native crossing op {op}")


class NativeSimulatedNetwork:
    """Drop-in for simulator.SimulatedNetwork backed by the C++ engine."""

    def __init__(
        self,
        public_keys: PublicConsensusKeys,
        private_keys: List[PrivateConsensusKeys],
        era: int = 0,
        seed: int = 0,
        mode: DeliveryMode = DeliveryMode.TAKE_FIRST,
        repeat_probability: float = 0.0,
        muted: Optional[Set[int]] = None,
        extra_factories=None,
        use_crypto_batcher: bool = True,
        use_rbc_batcher: bool = False,
        fault_plan=None,
        journals: Optional[List] = None,
        pipeline_window: int = 0,
        committee=None,
    ):
        # committee (consensus/committee_script.py): the other N-1 members of
        # the committee, hosted outside this process. The engine then hosts
        # validator 0 alone (private_keys holds its keys only); what it sends
        # them is handed to committee.react(records), whose answers enter the
        # engine in its own message types (rt_inject)
        self.n = public_keys.n
        self.committee = committee
        if committee is not None and (pipeline_window or fault_plan or muted):
            raise ValueError(
                "a committee network runs eras one at a time, without faults"
            )
        self.muted = set(muted or set())
        self.fault_plan = fault_plan
        if fault_plan is not None:
            # one FaultPlan, three delivery layers: here the plan maps onto
            # the engine's own fault knobs — duplication -> repeat_ppm,
            # reordering -> TAKE_RANDOM delivery, a crash that never
            # restarts -> a muted player. Features the engine cannot express
            # (probabilistic drop, delay, partitions, mid-era restart) are
            # refused loudly rather than silently weakened: a chaos run that
            # *looks* like it injected loss but didn't would certify a
            # recovery path that was never exercised.
            unsupported = []
            if fault_plan.drop > 0:
                unsupported.append("drop")
            if fault_plan.delay > 0:
                unsupported.append("delay")
            if fault_plan.partitions:
                unsupported.append("partitions")
            if any(c.restart is not None for c in fault_plan.crashes):
                unsupported.append("crash restart")
            if getattr(fault_plan, "shaper", None) is not None:
                unsupported.append("link shaper")
            if unsupported:
                raise ValueError(
                    "native engine cannot express FaultPlan feature(s): "
                    + ", ".join(unsupported)
                    + " — use the python simulator (engine='python') for "
                    "full fault injection"
                )
            if fault_plan.reorder > 0 and mode is DeliveryMode.TAKE_FIRST:
                mode = DeliveryMode.TAKE_RANDOM
            repeat_probability = max(
                repeat_probability, fault_plan.duplicate
            )
            seed = seed ^ (fault_plan.seed << 1)
            self.muted |= {c.node for c in fault_plan.crashes}
        self.mode = mode
        self._lib = load_rt()
        mode_i = {
            DeliveryMode.TAKE_FIRST: 0,
            DeliveryMode.TAKE_LAST: 1,
            DeliveryMode.TAKE_RANDOM: 2,
        }[mode]
        # engine-construction parameters are kept so the pipelined window
        # can instantiate ONE ENGINE PER IN-FLIGHT ERA: an engine has one
        # queue and one dispatch loop, so wall-clock overlap of era e's tail
        # with era e+1's front requires two independently pumpable engines.
        # Per-era engines also keep determinism trivial — each era's engine
        # sees exactly the event sequence a sequential run would feed it.
        self.f = public_keys.f
        self._mode_i = mode_i
        self._repeat_ppm = int(repeat_probability * 1_000_000)
        self._base_seed = seed & 0xFFFFFFFFFFFFFFFF
        self._coin_need = public_keys.ts_keys.t + 1
        self.pipeline_window = max(int(pipeline_window), 0)
        self._pipeline_active = False
        self._deferred: Dict[int, Dict[int, int]] = {}
        self._era_engines: Dict[int, int] = {}
        self._native_handled_closed = 0
        self._trace_dropped_closed = 0
        self._trace_backlog: List[dict] = []
        self._trace_capacity = 0
        self._phase_seen: Dict[int, List[int]] = {}  # handle -> ns by TP_*
        self._h = self._lib.rt_new(
            self.n,
            public_keys.f,
            mode_i,
            self._repeat_ppm,
            seed,
            era,
        )
        if not self._h:
            raise ValueError(
                f"native engine rejected N={self.n}: rt_new supports "
                "1 <= N <= 512 (512-bit membership masks)"
            )
        self._era_engines[era] = self._h
        for v in self.muted:
            self._lib.rt_mute(self._h, v)
        # threshold for the native coin's combine trigger (CommonCoin needs
        # t+1 shares before a combine can possibly succeed)
        self._lib.rt_set_coin_need(self._h, self._coin_need)
        hosted = range(1) if committee is not None else range(self.n)
        if committee is not None:
            if not self._lib._lt_has_seam:
                raise RuntimeError("the loaded consensus engine has no seam")
            for v in range(1, self.n):
                self._lib.rt_set_remote(self._h, v)
        self.routers: List[NativeEraRouter] = [
            NativeEraRouter(
                era=era,
                my_id=i,
                public_keys=public_keys,
                private_keys=private_keys[i],
                net=self,
                extra_factories=extra_factories,
                journal=journals[i] if journals is not None else None,
            )
            for i in hosted
        ]
        for r in self.routers:
            r.pipeline_window = self.pipeline_window
        # callback exceptions, stashed per era and re-raised from the pump
        # loop of the thread that owns that era's engine
        self._cb_errors: List[tuple] = []
        # keep CFUNCTYPE objects alive for the engine's lifetime; every
        # per-era engine shares the same set — callbacks carry the era, which
        # routes them to the right per-era host shims
        self._cbs = (
            _OPAQUE_CB(self._cb_opaque),
            _ACS_CB(self._cb_acs),
            _COINREQ_CB(self._cb_coinreq),
            _CROSS_CB(self._cb_cross),
        )
        self._lib.rt_set_callbacks(self._h, *self._cbs)
        self.delivered_count = 0
        # router-level TPKE flush batcher (crypto_batcher.py): flushed by
        # run() once every queued DecryptedMessage has been delivered — the
        # point where the cross-validator batch is largest
        self.crypto_batcher = None
        if use_crypto_batcher:
            from .crypto_batcher import TpkeEraBatcher

            self.crypto_batcher = TpkeEraBatcher()
            for r in self.routers:
                r.crypto_batcher = self.crypto_batcher
        # era-scoped RBC codec batcher (rbc_batcher.py): opt-in, and only
        # when the .so exports the version-7 RBC host boundary — a stale
        # library degrades to the engine's per-message RS path. LACHAIN_RBC_BATCH=0
        # force-disables it even when requested (ops kill switch).
        self.rbc_batcher = None
        self._rbc_host_on = False
        if (
            use_rbc_batcher
            and self._lib._lt_has_rbc_host
            and os.environ.get("LACHAIN_RBC_BATCH", "1") != "0"
        ):
            from .rbc_batcher import RbcEraBatcher

            self.rbc_batcher = RbcEraBatcher()
            self._rbc_host_on = True
            for r in self.routers:
                r.rbc_batcher = self.rbc_batcher
            self._lib.rt_set_rbc_host(self._h, 1)
        self._own_masks = [-1] * self.n  # engine-side mask cache (-1 unset)
        self._sync_ownership()
        # flight recorder: size the engine ring, align its clock with
        # time.monotonic, and register it with the merged tracer. A weakref
        # keeps the registry from pinning a leaked network alive; close()
        # unregisters explicitly.
        self._trace_offset = clock_offset(self._lib.rt_monotonic_ns)
        self._trace_dropped_seen = 0
        self._trace_source = f"consensus-{id(self):x}"
        self.trace_configure(tracing.capacity())
        ref = weakref.ref(self)
        tracing.register_native_source(
            self._trace_source,
            lambda: (
                [] if ref() is None else ref()._drain_trace()  # noqa: B023
            ),
            lambda n: None if ref() is None else ref().trace_configure(n),
        )

    # -- per-era engine lifecycle ---------------------------------------------
    def _live_engines(self) -> List[int]:
        hs: List[int] = []
        if self._h is not None:
            hs.append(self._h)
        for h in self._era_engines.values():
            if h not in hs:
                hs.append(h)
        return hs

    def _h_for(self, era: Optional[int]) -> Optional[int]:
        """Engine handle for `era`: the per-era engine when the pipeline
        window is active, the single shared engine otherwise. None means the
        era's engine is already closed — its traffic is settled and posts
        for it are dropped, mirroring the stale-era drop."""
        if self._pipeline_active and era is not None:
            return self._era_engines.get(era)
        return self._h

    def _era_seed(self, era: int) -> int:
        # deterministic per-era engine seed: two runs with the same base
        # seed get byte-identical delivery schedules era by era
        return (self._base_seed ^ (era * 0x9E3779B97F4A7C15)) & (
            (1 << 64) - 1
        )

    def _open_era_engine(self, era: int) -> None:
        if era in self._era_engines:
            return
        # engines are constructed on the scheduler thread only: the GF(256)
        # table bootstrap in consensus_rt.cpp is guarded by a plain static
        # flag, so first-construction must never race across threads
        h = self._lib.rt_new(
            self.n, self.f, self._mode_i, self._repeat_ppm,
            self._era_seed(era), era,
        )
        if not h:
            raise ValueError(
                f"native engine rejected N={self.n}: rt_new supports "
                "1 <= N <= 512 (512-bit membership masks)"
            )
        for v in self.muted:
            self._lib.rt_mute(h, v)
        self._lib.rt_set_coin_need(h, self._coin_need)
        if self._rbc_host_on:
            self._lib.rt_set_rbc_host(h, 1)
        self._lib.rt_set_callbacks(h, *self._cbs)
        for vid in range(self.n):
            if self._own_masks[vid] >= 0:
                self._lib.rt_set_owned(h, vid, self._own_masks[vid])
        self._lib.rt_trace_configure(h, max(int(self._trace_capacity), 0))
        self._era_engines[era] = h

    def _close_era_engine(self, era: int) -> None:
        h = self._era_engines.pop(era, None)
        if h is None or h == self._h:
            # the construction-time engine doubles as the legacy single-era
            # handle; keep it alive (quiescent) for the aggregate accessors
            return
        try:
            self._trace_backlog.extend(self._drain_engine_trace(h))
        except Exception:  # pragma: no cover - tracing must never kill an era
            pass
        # a later engine may get the same address
        self._phase_seen.pop(h, None)
        self._native_handled_closed += int(self._lib.rt_native_handled(h))
        self._trace_dropped_closed += int(self._lib.rt_trace_dropped(h))
        self._lib.rt_free(h)

    # -- flight recorder -------------------------------------------------------
    def trace_configure(self, capacity: int) -> None:
        """Resize the engine-side trace rings; 0 disables recording (and
        the hot-path clock reads) entirely."""
        self._trace_capacity = max(int(capacity), 0)
        for h in self._live_engines():
            self._lib.rt_trace_configure(h, self._trace_capacity)

    def _fold_dispatch(self, h: int) -> Dict[str, float]:
        """What engine `h` spent dispatching since the last call, in
        seconds by family: added to DISPATCH_METRIC and returned. Read from
        the engine's running totals, not from its ring: nothing evicts
        them, and they stand still while recording is off."""
        now = (ctypes.c_uint64 * 8)()
        self._lib.rt_phase_totals(h, now)
        seen = self._phase_seen.setdefault(h, [0] * 8)
        moved: Dict[str, float] = {}
        for ph, family in TP_NAMES.items():
            if now[ph] > seen[ph]:
                moved[family] = (now[ph] - seen[ph]) / 1e9
                metrics.inc(
                    DISPATCH_METRIC, moved[family], labels={"family": family}
                )
                seen[ph] = now[ph]
        return moved

    def trace_dropped(self) -> int:
        total = self._trace_dropped_closed
        for h in self._live_engines():
            total += int(self._lib.rt_trace_dropped(h))
        return total

    def _drain_engine_trace(self, h: int) -> List[dict]:
        # size query, then copying call; the copy consumes the ring. Slack
        # covers records appended between the two calls; if the ring still
        # outgrew the buffer (got > len(buf) means no copy happened), retry.
        for _ in range(4):
            need = self._lib.rt_trace_drain(h, None, 0)
            if need == 0:
                return []
            buf = (ctypes.c_uint8 * (need + 4096))()
            got = self._lib.rt_trace_drain(h, buf, len(buf))
            if got <= len(buf):
                return decode_consensus_trace(
                    bytes(buf[:got]), self._trace_offset
                )
        return []

    def _drain_trace(self) -> List[dict]:
        """Consume the engine rings -> merged-tracer event dicts. Publishes
        native drop-counter growth as a counter delta so
        trace_events_dropped_total keeps counter semantics. While the
        pipeline window is live, only the backlog of CLOSED era engines is
        served: draining a ring that another thread is appending to would
        race inside the engine, so live rings wait for pipeline_end."""
        evs, self._trace_backlog = self._trace_backlog, []
        if not self._pipeline_active:
            for h in self._live_engines():
                evs.extend(self._drain_engine_trace(h))
        dropped = self.trace_dropped()
        if dropped > self._trace_dropped_seen:
            metrics.inc(
                "trace_events_dropped_total",
                dropped - self._trace_dropped_seen,
                labels={"source": "consensus"},
            )
            self._trace_dropped_seen = dropped
        return evs

    def close(self) -> None:
        if self._h is not None or self._era_engines:
            # pull any still-buffered engine events into the merged tracer
            # before the rings are freed
            self._pipeline_active = False
            try:
                tracing.drain_native()
            except Exception:
                pass
            self._free_engines()

    def _free_engines(self) -> None:
        tracing.unregister_native_source(self._trace_source)
        for h in self._live_engines():
            self._lib.rt_free(h)
        self._era_engines = {}
        self._h = None

    def __del__(self):  # pragma: no cover
        # the collector runs this wherever it interrupts a thread, also
        # inside the metrics registry's lock (metrics.measure): a drain
        # records metrics and would wait on that lock for ever, so a
        # network nobody closed loses its undrained ring events instead
        try:
            if self._h is not None or self._era_engines:
                self._free_engines()
        except Exception:
            pass

    # -- native ownership ------------------------------------------------------
    def _era_fn_available(self) -> bool:
        from ..crypto.provider import get_backend

        return (
            getattr(get_backend(), "tpke_era_verify_combine", None) is not None
        )

    def _sync_owner(self, vid: int) -> None:
        mask = self.routers[vid]._native_mask()
        if mask != self._own_masks[vid]:
            self._own_masks[vid] = mask
            for h in self._live_engines():
                self._lib.rt_set_owned(h, vid, mask)

    def _sync_ownership(self) -> None:
        for r in self.routers:
            self._sync_owner(r._my_id)

    def set_root_context(self, vid: int, producer, ecdsa_priv, ecdsa_pubs) -> None:
        """Give validator `vid` its block-production context so RootProtocol
        can be hosted natively (the Python fallback uses the same context)."""
        self.routers[vid]._root_ctx = (producer, ecdsa_priv, ecdsa_pubs)
        self._sync_owner(vid)

    # -- engine entry points ---------------------------------------------------
    # Each takes era=None and routes to that era's engine via _h_for. A None
    # handle means the era's engine already closed (its block committed and
    # settled traffic is still draining through host shims) — the post is
    # dropped, exactly like the router's stale-era drop.
    def _post_acs_input(self, vid: int, data: bytes, era: int = None) -> None:
        h = self._h_for(era)
        if h is not None:
            self._lib.rt_post_acs_input(h, vid, data, len(data))

    def _post_coin_result(
        self, vid: int, agreement: int, epoch: int, value, era: int = None
    ) -> None:
        h = self._h_for(era)
        if h is not None:
            self._lib.rt_post_coin_result(
                h, vid, agreement, epoch, 1 if value else 0
            )

    def _bcast_opaque(
        self,
        vid: int,
        kind: int,
        agreement: int,
        epoch: int,
        data: bytes,
        era: int = None,
    ) -> None:
        h = self._h_for(era)
        if h is not None:
            self._lib.rt_broadcast_opaque(
                h, vid, kind, agreement, epoch, data, len(data)
            )

    def _send_opaque(
        self,
        vid: int,
        target: int,
        kind: int,
        agreement: int,
        epoch: int,
        data: bytes,
        era: int = None,
    ) -> None:
        # unicast opaque injection: the adversary layer's transport (the
        # caller chooses `vid`, so sender spoofing / replay is expressible)
        h = self._h_for(era)
        if h is not None:
            self._lib.rt_send_opaque(
                h, vid, target, kind, agreement, epoch, data, len(data)
            )

    def _rt_request(self, vid: int, kind: int, a: int, b: int, era: int = None) -> None:
        h = self._h_for(era)
        if h is None:
            return
        self._lib.rt_request(h, vid, kind, a, b)
        # a request posted OUTSIDE run() (post_request path) can recurse
        # through the engine into host code; surface its failure now
        self._raise_cb_error(era)

    def _rt_post(
        self, vid: int, op: int, a: int, b: int, data: bytes = b"", era: int = None
    ) -> None:
        h = self._h_for(era)
        if h is not None:
            self._lib.rt_post(h, vid, op, a, b, data, len(data))

    def _rt_hb_export(self, vid: int, era: int = None) -> bytes:
        h = self._h_for(era)
        if h is None:
            return b""
        size = self._lib.rt_hb_ready_export(h, vid, None, 0)
        if not size:
            return b""
        buf = ctypes.create_string_buffer(size)
        self._lib.rt_hb_ready_export(h, vid, buf, size)
        return buf.raw[:size]

    def native_state_of(self, vid: int, era: int = None) -> str:
        def one(h):
            size = self._lib.rt_debug_state(h, vid, None, 0)
            if not size:
                return ""
            buf = ctypes.create_string_buffer(size)
            self._lib.rt_debug_state(h, vid, buf, size)
            return buf.raw[:size].decode("utf-8", "replace")

        if self._pipeline_active and era is None:
            # stall reports want the whole window, labeled per era
            parts = [
                f"era{e}:{one(h)}"
                for e, h in sorted(self._era_engines.items())
            ]
            return " | ".join(parts)
        h = self._h_for(era)
        return one(h) if h is not None else ""

    def native_handled(self) -> int:
        """Messages the engine consumed natively that PREVIOUSLY each cost a
        per-message Python callback — the eliminated crossings."""
        total = self._native_handled_closed
        for h in self._live_engines():
            total += int(self._lib.rt_native_handled(h))
        return total

    def _advance_era(self, vid: int, era: int) -> None:
        self._lib.rt_advance_era(self._h, vid, era)

    def _request_stop(self, era: int = None) -> None:
        if self._pipeline_active and era is None:
            for h in self._live_engines():
                self._lib.rt_request_stop(h)
            return
        h = self._h_for(era)
        if h is not None:
            self._lib.rt_request_stop(h)

    def mute(self, vid: int) -> None:
        self.muted.add(vid)
        for h in self._live_engines():
            self._lib.rt_mute(h, vid)

    # -- callbacks (engine -> Python); exceptions are stashed per era and
    #    re-raised from the pump loop of the thread owning that era's engine,
    #    since they cannot unwind through the C++ frames ----------------------
    def _stash_cb_error(self, era, exc) -> None:
        self._cb_errors.append((era, exc))

    def _pop_cb_error(self, era=None) -> Optional[BaseException]:
        """Take the first stashed error for `era` (None matches any — the
        sequential path, where one thread owns every engine)."""
        for i, (e, exc) in enumerate(self._cb_errors):
            if era is None or e == era or e is None:
                del self._cb_errors[i]
                return exc
        return None

    def _raise_cb_error(self, era=None) -> None:
        err = self._pop_cb_error(era)
        if err is not None:
            raise err

    def _cb_opaque(self, target, sender, era, kind, agreement, epoch, data, length):
        if self._cb_errors:
            return
        sid = _cross_begin("opaque_message", era, target)
        try:
            blob = ctypes.string_at(data, length) if length else b""
            self.routers[target]._on_opaque(
                sender, era, kind, agreement, epoch, blob
            )
            if kind == KIND_DECRYPTED and self.crypto_batcher is not None:
                h = self._h_for(era)
                if (
                    h is not None
                    and self.crypto_batcher.pending_for(era)
                    and self._lib.rt_opaque_pending(h, KIND_DECRYPTED) == 0
                ):
                    # all decryption shares delivered: break out so the pump
                    # loop can flush the cross-validator batch before
                    # lag-round traffic
                    self._lib.rt_request_stop(h)
        except BaseException as exc:  # noqa: BLE001
            self._stash_cb_error(era, exc)
        finally:
            tracing.end(sid)

    def _cb_acs(self, target, era, nslots, slots, datas, lens):
        if self._cb_errors:
            return
        sid = _cross_begin("acs_result", era, target)
        try:
            result = {
                int(slots[i]): (
                    ctypes.string_at(datas[i], lens[i]) if lens[i] else b""
                )
                for i in range(nslots)
            }
            self.routers[target]._on_acs_result(era, result)
        except BaseException as exc:  # noqa: BLE001
            self._stash_cb_error(era, exc)
        finally:
            tracing.end(sid)

    def _cb_coinreq(self, target, era, agreement, epoch):
        if self._cb_errors:
            return
        sid = _cross_begin("coin_request", era, target)
        try:
            self.routers[target]._on_coin_request(era, agreement, epoch)
        except BaseException as exc:  # noqa: BLE001
            self._stash_cb_error(era, exc)
        finally:
            tracing.end(sid)

    def _cb_cross(self, target, era, op, a, b, data, length):
        if self._cb_errors:
            return
        sid = _cross_begin(XO_NAMES.get(op, f"op{op}"), era, target)
        try:
            blob = ctypes.string_at(data, length) if length else b""
            self.routers[target]._on_cross(era, op, a, b, blob)
        except BaseException as exc:  # noqa: BLE001
            self._stash_cb_error(era, exc)
        finally:
            tracing.end(sid)

    # -- execution (simulator.py::run contract) --------------------------------
    def _run_engine(self, h: int, chunk: int, era: int) -> int:
        """One rt_run call under the span `engine.pump`: the engine's own
        dispatch and every callback it makes meanwhile (cross.* inside).
        Only rt_run moves the engine's dispatch totals, so they are folded
        here, and the span carries what this call added by family: the
        era's share of DISPATCH_METRIC, which tracing.era_report reads."""
        sid = tracing.begin("engine.pump", "engine", era=era)
        processed = self._lib.rt_run(h, chunk)
        tracing.end(
            sid, processed=processed, dispatch_s=self._fold_dispatch(h)
        )
        return processed

    def _answer_committee(self) -> bool:
        """Hand what the hosted validator sent the committee since the last
        call to committee.react and queue its answers; True when any were
        queued."""
        size = self._lib.rt_out_drain(self._h, None, 0)
        if not size:
            return False
        buf = (ctypes.c_uint8 * size)()
        got = self._lib.rt_out_drain(self._h, buf, size)
        queued = 0
        for era, blob, count in self.committee.react(
            decode_seam_records(bytes(buf)[:got])
        ):
            n = self._lib.rt_inject(self._h, era, blob, len(blob))
            if n != count:
                raise RuntimeError(
                    f"the engine queued {n} of {count} committee messages"
                )
            queued += n
        return queued > 0

    def post_request(self, validator: int, pid, value) -> None:
        self._sync_ownership()
        # proposal injection does the RBC encode (erasure coding) before
        # the first dispatch chunk runs — outside the engine's dispatch
        # totals, so tag it as propose-phase work here
        with tracing.span(
            "consensus.propose", era=getattr(pid, "era", None)
        ):
            self.routers[validator].internal_request(
                M.Request(from_id=None, to_id=pid, input=value)
            )

    def run(
        self,
        done: Callable[[], bool],
        max_messages: int = 1_000_000,
        chunk: int = 16384,
    ) -> bool:
        try:
            while not done():
                processed = self._run_engine(
                    self._h, chunk, self.routers[0].era
                )
                self.delivered_count += processed
                self._raise_cb_error()
                # the committee answers what validator 0 sent before any
                # batch flushes: its answers can only make the batches larger
                if self.committee is not None and self._answer_committee():
                    continue
                metrics.set_gauge(
                    "consensus_dispatch_queue_depth",
                    self._lib.rt_queue_len(self._h),
                )
                # RBC codec batch flushes first: interpolations unblock
                # READY/deliver and thus ACS, so draining them before the
                # TPKE flush keeps the later crypto batch as large as it
                # can possibly get
                if (
                    self.rbc_batcher is not None
                    and self.rbc_batcher.pending
                    and self._lib.rt_queue_len(self._h) == 0
                ):
                    self.rbc_batcher.flush()
                    self._raise_cb_error()
                    continue
                if (
                    self.crypto_batcher is not None
                    and self.crypto_batcher.pending
                    and (
                        self._lib.rt_queue_len(self._h) == 0
                        or self._lib.rt_opaque_pending(self._h, KIND_DECRYPTED)
                        == 0
                    )
                ):
                    self.crypto_batcher.flush()
                    self._raise_cb_error()
                    continue
                if processed == 0:
                    return done()
                if (
                    self.delivered_count >= max_messages
                    and self._lib.rt_queue_len(self._h) > 0
                    and not done()
                ):
                    raise RuntimeError(
                        f"message cap {max_messages} exceeded — livelock?"
                    )
            return True
        finally:
            metrics.set_gauge(
                "consensus_native_handled_messages", self.native_handled()
            )

    # -- pipelined window (era overlap) ----------------------------------------
    # The windowed scheduler (core/devnet.py) splits every era at the
    # XO_ROOT_SIGN crossing: the FRONT (propose/encrypt/RBC/BA/coin/
    # TPKE-verify-combine) runs on the scheduler thread; the TAIL (header
    # sign + flood + ECDSA verify + produce/commit) runs on a worker thread
    # that commits eras strictly ascending. Each per-era engine is pumped by
    # exactly one thread at a time: the scheduler hands the engine to the
    # tail worker at front-complete and never touches it again.
    def pipeline_begin(self) -> None:
        if self.pipeline_window < 1:
            raise RuntimeError("pipeline_begin requires pipeline_window >= 1")
        self._sync_ownership()
        full = OWN_HB | OWN_COIN | OWN_ROOT
        for r in self.routers:
            if r._native_mask() != full:
                raise RuntimeError(
                    "era pipelining requires full native ownership on every "
                    f"validator (validator {r._my_id} mask "
                    f"{r._native_mask():#x}) — python-protocol overrides must "
                    "run sequentially"
                )
        self._pipeline_active = True
        self._deferred = {}

    def pipeline_end(self) -> None:
        self._pipeline_active = False
        self._deferred = {}

    def open_era(self, era: int) -> None:
        """Admit `era` into the window: give it an engine (scheduler thread
        only — see _open_era_engine) and forward every router."""
        self._open_era_engine(era)
        for r in self.routers:
            r.open_era(era)

    def commit_era(self, era: int) -> None:
        """Called by the tail worker after `era`'s block committed: journal
        GC honoring the overlap window, then retire the era's engine."""
        for r in self.routers:
            r.commit_era_gc(era)
        self._deferred.pop(era, None)
        self._close_era_engine(era)

    def _defer_sign(self, vid: int, era: int, parity: int) -> bool:
        """XO_ROOT_SIGN interception point. Outside the pipelined window:
        decline (the host signs inline). Inside: stash the coin parity —
        era `era`'s front is complete for `vid` — and once all n validators
        reach the sign point, break the engine out of its chunk so run_front
        can return. Muted validators still reach the sign point (they
        receive everything; muting only gags their sends)."""
        if not self._pipeline_active:
            return False
        d = self._deferred.setdefault(era, {})
        d[vid] = parity
        if len(d) >= self.n:
            h = self._era_engines.get(era)
            if h is not None:
                self._lib.rt_request_stop(h)
        return True

    def front_complete(self, era: int) -> bool:
        return len(self._deferred.get(era, ())) >= self.n

    def _pump(
        self, era: int, lane: str, done: Callable[[], bool],
        max_messages: int, chunk: int,
    ) -> None:
        """Shared pump loop for one era's engine on one lane. Flushes ONLY
        this era's crypto batches (pending_for/flush(era)): lazy builders
        rt_post into their era's engine, so only the thread owning that
        engine may flush its submissions."""
        h = self._era_engines.get(era)
        if h is None:
            raise RuntimeError(f"era {era} engine is not open")
        delivered = 0
        while not done():
            processed = self._run_engine(h, chunk, era)
            delivered += processed
            self.delivered_count += processed
            self._raise_cb_error(era)
            metrics.set_gauge(
                "consensus_dispatch_queue_depth", self._lib.rt_queue_len(h)
            )
            if (
                self.rbc_batcher is not None
                and self.rbc_batcher.pending_for(era)
                and self._lib.rt_queue_len(h) == 0
            ):
                self.rbc_batcher.flush(era)
                self._raise_cb_error(era)
                continue
            if (
                self.crypto_batcher is not None
                and self.crypto_batcher.pending_for(era)
                and (
                    self._lib.rt_queue_len(h) == 0
                    or self._lib.rt_opaque_pending(h, KIND_DECRYPTED) == 0
                )
            ):
                self.crypto_batcher.flush(era)
                self._raise_cb_error(era)
                continue
            if processed == 0:
                # in the simulator there is no external input: an idle
                # engine with nothing to flush and the lane not done is a
                # genuine wedge
                raise RuntimeError(self._stall_report(era, lane))
            if delivered >= max_messages and self._lib.rt_queue_len(h) > 0:
                raise RuntimeError(
                    f"era {era} {lane}: message cap {max_messages} "
                    "exceeded — livelock?"
                )

    def run_front(
        self, era: int, max_messages: int = 2_000_000, chunk: int = 16384
    ) -> None:
        """Pump era `era` until every validator's front is complete (all n
        sign-deferred). Scheduler thread only."""
        self._pump(
            era, "front", lambda: self.front_complete(era),
            max_messages, chunk,
        )

    def run_tail(
        self, era: int, max_messages: int = 2_000_000, chunk: int = 16384
    ) -> List[Any]:
        """Resume the deferred signs and pump era `era` to block production
        on every router. Tail-worker thread only; eras strictly ascending."""
        pid = M.RootProtocolId(era=era)
        deferred = self._deferred.get(era, {})
        for vid in range(self.n):
            self.routers[vid].root_host(era).on_sign(deferred[vid])
            self._raise_cb_error(era)

        def tail_done() -> bool:
            return all(pid in r._native_results for r in self.routers)

        self._pump(era, "tail", tail_done, max_messages, chunk)
        return [r._native_results[pid] for r in self.routers]

    def _stall_report(self, era: int, lane: str) -> str:
        in_flight = sorted(self._era_engines)
        lines = [
            f"consensus pipeline stalled: era {era} ({lane} lane) wedged; "
            f"in-flight eras {in_flight}"
        ]
        for vid in range(self.n):
            lines.append(
                f"  validator {vid}: {self.native_state_of(vid, era=era)}"
            )
        return "\n".join(lines)

    def results(self, pid) -> List[Any]:
        return [r.result_of(pid) for r in self.routers]
