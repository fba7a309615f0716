"""Host shims for the natively-hosted crypto protocols.

The C++ engine (native/consensus_rt.cpp) owns the MESSAGE state machines of
CommonCoin, HoneyBadger and RootProtocol — dedupe, thresholds, stashes,
result routing — while these shims own every cryptographic operation: BLS
threshold signing/combining, TPKE encrypt/decrypt-share/verify/combine, and
ECDSA header signatures. The two halves talk through BATCHED crossings (one
generic callback op covers many messages: all pending coin shares, all ready
decrypt-share slots, all unverified header signatures), which is what removes
the per-message Python callback cost from the era hot path.

Each shim mirrors its oracle class (common_coin.py / honey_badger.py /
root_protocol.py) statement-for-statement on the crypto side, reusing the
exact same primitives, so a TAKE_FIRST native run stays bit-identical to the
Python engine — tests/test_native_rt.py pins that equality.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from ..crypto import ecdsa, tpke
from ..crypto import threshold_sig as ts
from ..utils import tracing
from . import messages as M

# --- shared contract with consensus_rt.cpp (enums CrossOp/PostOp/ReqKind) ---

# engine -> Python crossing ops
XO_COIN_SIGN = 1
XO_COIN_COMBINE = 2
XO_COIN_RESULT = 3
XO_HB_ACS = 4
XO_HB_QUEUE = 5
XO_HB_DONE = 6
XO_ROOT_INPUT = 7
XO_ROOT_SIGN = 8
XO_ROOT_VERIFY = 9
XO_ROOT_PRODUCE = 10
XO_EVIDENCE = 11
XO_RBC_ENCODE = 12
XO_RBC_NEED = 13

XO_NAMES = {
    XO_COIN_SIGN: "coin_sign",
    XO_COIN_COMBINE: "coin_combine",
    XO_COIN_RESULT: "coin_result",
    XO_HB_ACS: "hb_acs",
    XO_HB_QUEUE: "hb_queue",
    XO_HB_DONE: "hb_done",
    XO_ROOT_INPUT: "root_input",
    XO_ROOT_SIGN: "root_sign",
    XO_ROOT_VERIFY: "root_verify",
    XO_ROOT_PRODUCE: "root_produce",
    XO_EVIDENCE: "evidence",
    XO_RBC_ENCODE: "rbc_encode",
    XO_RBC_NEED: "rbc_need",
}

# Python -> engine post ops
PO_COIN_SHARE = 1
PO_COIN_RESULT = 2
PO_HB_ACS_INPUT = 3
PO_HB_DECRYPTED = 4
PO_HB_ACS_DONE = 5
PO_HB_RESOLVED = 6
PO_HB_REJECT = 7
PO_HB_SET_INFLIGHT = 8
PO_HB_CLEAR_INFLIGHT = 9
PO_HB_CLEAR_QUEUED = 10
PO_HB_REQUEUE_CHECK = 11
PO_ROOT_HEADER = 12
PO_ROOT_ACCEPT = 13
PO_ROOT_REJECT = 14
PO_RBC_VALS = 15
PO_RBC_RESULT = 16

# rt_request kinds
RQ_HB = 1
RQ_COIN = 2
RQ_ROOT = 3


def iter_pairs(blob: bytes) -> List[Tuple[int, bytes]]:
    """Decode the engine's (u32 id, u32 len, bytes)* big-endian framing."""
    out = []
    off = 0
    end = len(blob)
    while off + 8 <= end:
        ident = int.from_bytes(blob[off : off + 4], "big")
        ln = int.from_bytes(blob[off + 4 : off + 8], "big")
        off += 8
        out.append((ident, blob[off : off + ln]))
        off += ln
    return out


class CoinHost:
    """Crypto half of a native CommonCoin (common_coin.py oracle): owns the
    ThresholdSigner; share dedupe/threshold/routing live in the engine."""

    def __init__(self, router, cid: M.CoinId):
        self.router = router
        self.cid = cid
        self._signer = ts.ThresholdSigner(
            cid.to_bytes(),
            router.private_keys.ts_share,
            router.public_keys.ts_keys,
        )
        self._flagged: set = set()  # senders already reported as evidence

    def sign(self) -> None:
        # common_coin.py::handle_input — the engine broadcasts + records the
        # share and runs its combine check inside the rt_post call
        my_share = self._signer.sign()
        payload = M.CoinMessage(coin=self.cid, share=my_share.to_bytes())
        wire = self.router._native_send(payload)
        self._signer.add_share(my_share, verify=False)
        self.router._net._rt_post(
            self.router.my_id,
            PO_COIN_SHARE,
            self.cid.agreement,
            self.cid.epoch,
            wire.share,
            era=self.cid.era,
        )

    def combine(self, blob: bytes) -> None:
        # common_coin.py::_try_combine crypto half: one batched G2 parse for
        # every share the engine has not shipped yet, then evaluate the
        # combined signature (deferred verification, prune on failure)
        pending = iter_pairs(blob)
        if pending:
            from ..crypto import bls12381 as bls
            from ..crypto.provider import deserialize_batch_g2

            pts = deserialize_batch_g2(
                [data[: bls.G2_BYTES] for _, data in pending]
            )
            for (sender, _), pt in zip(pending, pts):
                if pt is None:
                    self._flag_invalid(sender)
                    continue  # malformed/bad-subgroup share: drop
                self._signer.add_share(
                    ts.PartialSignature(sigma=pt, signer_id=sender),
                    verify=False,
                )
        sig = self._signer.signature
        # common_coin.py::_try_combine: batch-verifier prunes are evidence
        for sender in self._signer.pruned - self._flagged:
            self._flag_invalid(sender)
        if sig is not None:
            self.router._net._rt_post(
                self.router.my_id,
                PO_COIN_RESULT,
                self.cid.agreement,
                self.cid.epoch,
                bytes([1 if sig.parity else 0]),
                era=self.cid.era,
            )

    def _flag_invalid(self, sender: int) -> None:
        if sender in self._flagged:
            return
        self._flagged.add(sender)
        ev = getattr(self.router, "evidence", None)
        if ev is not None:
            ev.record_invalid_share(
                self.cid.era,
                sender,
                "coin",
                (self.cid.agreement, self.cid.epoch),
            )


class HoneyBadgerHost:
    """Crypto half of a native HoneyBadger (honey_badger.py oracle): TPKE
    encrypt/decode/verify/decrypt + the era-batcher build/apply protocol.
    The engine mirrors share candidates; `_cands` is this side's snapshot,
    refreshed from the engine at every batch build."""

    def __init__(self, router, era: int):
        self.router = router
        self.id = M.HoneyBadgerId(era=era)
        self._pub = router.public_keys
        self._priv = router.private_keys
        self.me = router.my_id
        self.n = self._pub.n
        self._ciphertexts: Dict[int, tpke.EncryptedShare] = {}
        self._plaintexts: Dict[int, Optional[bytes]] = {}
        self._parsed: Dict[Tuple[int, int], tpke.PartiallyDecryptedShare] = {}
        self._cands: Dict[int, Dict[int, bytes]] = {}
        self._lag_cache: Dict[Tuple[int, ...], list] = {}
        self.done = False
        self.result: Optional[dict] = None

    def _post(self, op: int, a: int = 0, b: int = 0, data: bytes = b"") -> None:
        self.router._net._rt_post(
            self.router.my_id, op, a, b, data, era=self.id.era
        )

    # -- input ---------------------------------------------------------------
    def handle_input(self, value: bytes) -> None:
        enc = self._pub.tpke_pub.encrypt(value, share_id=self.me)
        self._post(PO_HB_ACS_INPUT, data=enc.to_bytes())

    # -- ACS result (XO_HB_ACS) ----------------------------------------------
    def on_acs(self, blob: bytes) -> None:
        # honey_badger.py::handle_child_result crypto half. Slot order in the
        # blob is ascending (engine), matching the oracle's sorted(value)
        items = iter_pairs(blob)
        decoded = tpke.decode_encrypted_shares_batch([d for _, d in items])
        parsed: Dict[int, tpke.EncryptedShare] = {}
        for (slot, _), share in zip(items, decoded):
            if share is None:
                # proposer shipped garbage through RBC: slot yields nothing
                self._plaintexts[slot] = None
                self._post(PO_HB_RESOLVED, a=slot)
            else:
                parsed[slot] = share
        slots = sorted(parsed)
        oks = tpke.batch_verify_ciphertexts([parsed[s] for s in slots])
        valid = []
        for slot, ok in zip(slots, oks):
            if not ok:
                self._plaintexts[slot] = None
                self._post(PO_HB_RESOLVED, a=slot)
                continue
            self._ciphertexts[slot] = parsed[slot]
            valid.append(slot)
        # one threaded backend call for all U^{x_i} muls instead of one
        # native crossing per slot (same math, same emission order)
        decs = tpke.decrypt_shares_batch(
            self._priv.tpke_priv, [parsed[s] for s in valid]
        )
        for slot, dec in zip(valid, decs):
            payload = M.DecryptedMessage(
                hb=self.id, share_id=slot, payload=dec.to_bytes()
            )
            wire = self.router._native_send(payload)
            self._parsed[(slot, self.me)] = dec
            self._post(PO_HB_DECRYPTED, a=slot, data=wire.payload)
        self._post(PO_HB_ACS_DONE)

    # -- batcher protocol (XO_HB_QUEUE -> lazy build -> results cb) ----------
    def on_queue(self) -> None:
        self.router.crypto_batcher.submit_lazy(
            self._build_era_jobs_lazy, era=self.id.era
        )
        tracing.instant("hb.queue_decrypt", cat="crypto", era=self.id.era)

    def _refresh_cands(self) -> List[int]:
        """Pull the engine's ready slots + candidate shares; returns the
        ready slot list (ascending, the oracle's _ready_slots order)."""
        blob = self.router._net._rt_hb_export(
            self.router.my_id, era=self.id.era
        )
        ready = []
        off = 0
        end = len(blob)
        while off + 8 <= end:
            slot = int.from_bytes(blob[off : off + 4], "big")
            nsenders = int.from_bytes(blob[off + 4 : off + 8], "big")
            off += 8
            cands: Dict[int, bytes] = {}
            for _ in range(nsenders):
                sender = int.from_bytes(blob[off : off + 4], "big")
                ln = int.from_bytes(blob[off + 4 : off + 8], "big")
                off += 8
                cands[sender] = blob[off : off + ln]
                off += ln
            self._cands[slot] = cands
            ready.append(slot)
        return ready

    def _build_era_jobs_lazy(self):
        self._post(PO_HB_CLEAR_QUEUED)
        if self.done:
            return None
        return self._build_era_jobs()

    def _build_era_jobs(self):
        # honey_badger.py::_build_era_jobs, with the ready/candidate state
        # exported from the engine instead of self._shares
        from ..crypto import bls12381 as bls
        from ..crypto.tpu_backend import EraSlotJob

        need = self._pub.f + 1
        while True:
            ready = self._refresh_cands()
            if not ready:
                return None
            chosen_by_slot = {
                s: sorted(self._cands[s])[:need] for s in ready
            }
            wanted = [(s, i) for s in ready for i in chosen_by_slot[s]]
            if self._parse_shares(wanted) == 0:
                break
        jobs = []
        for slot in ready:
            ct = self._ciphertexts[slot]
            chosen = chosen_by_slot[slot]
            key = tuple(chosen)
            cs = self._lag_cache.get(key)
            if cs is None:
                cs = bls.fr_lagrange_coeffs([i + 1 for i in chosen], at=0)
                self._lag_cache[key] = cs
            lag_row = [0] * self.n
            u_row = [None] * self.n
            for i, c in zip(chosen, cs):
                lag_row[i] = c
                u_row[i] = self._parsed[(slot, i)].ui
            jobs.append(
                EraSlotJob(
                    u_by_validator=u_row,
                    lagrange_row=lag_row,
                    h=tpke.ciphertext_h(ct),
                    w=ct.w,
                )
            )
        for slot in ready:
            self._post(PO_HB_SET_INFLIGHT, a=slot)
        return (
            jobs,
            self._pub.tpke_verification_keys,
            lambda results, _ready=tuple(ready): self._era_results_cb(
                _ready, results
            ),
        )

    def _era_results_cb(self, ready, results) -> None:
        for slot in ready:
            self._post(PO_HB_CLEAR_INFLIGHT, a=slot)
        if self.done:
            return
        with tracing.span(
            "hb.apply_era_results",
            cat="crypto",
            era=self.id.era,
            slots=len(ready),
        ):
            for slot, (ok, combined) in zip(ready, results):
                if ok:
                    self._resolve(
                        slot,
                        tpke.decrypt_with_combined(
                            self._ciphertexts[slot], combined
                        ),
                    )
                else:
                    # the batch REJECTED the slot: prune the bad share on
                    # the per-share host path
                    self._try_decrypt(slot)
        self._post(PO_HB_REQUEUE_CHECK)

    def _resolve(self, slot: int, plaintext: Optional[bytes]) -> None:
        self._plaintexts[slot] = plaintext
        self._post(PO_HB_RESOLVED, a=slot)

    def _parse_shares(self, wanted) -> int:
        # honey_badger.py::_parse_shares over the engine-candidate mirror;
        # failures prune BOTH sides (engine reject + local mirror)
        missing = [k for k in wanted if k not in self._parsed]
        if not missing:
            return 0
        from ..crypto import bls12381 as bls
        from ..crypto.provider import deserialize_batch_g1

        datas = [
            self._cands[slot][sender][: bls.G1_BYTES]
            for slot, sender in missing
        ]
        pts = deserialize_batch_g1(datas)
        failures = 0
        for (slot, sender), pt in zip(missing, pts):
            if pt is None:
                failures += 1
                del self._cands[slot][sender]
                self._post(PO_HB_REJECT, a=slot, b=sender)
                self._flag_invalid(sender, slot)
            else:
                self._parsed[(slot, sender)] = tpke.PartiallyDecryptedShare(
                    ui=pt, decryptor_id=sender, share_id=slot
                )
        return failures

    def _try_decrypt(self, slot: int) -> None:
        # honey_badger.py::_try_decrypt (per-slot host path: prunes bad shares)
        if slot in self._plaintexts:
            return
        need = self._pub.f + 1
        slot_shares = self._cands.get(slot, {})
        if len(slot_shares) < need:
            return
        self._parse_shares([(slot, i) for i in sorted(slot_shares)])
        if len(slot_shares) < need:
            return  # parse failures shrank the candidate set
        ct = self._ciphertexts[slot]
        decryptors = sorted(slot_shares)
        decs = [self._parsed[(slot, i)] for i in decryptors]
        vks = [self._pub.tpke_verification_keys[i] for i in decryptors]
        oks = self._pub.tpke_pub.batch_verify_shares(vks, decs, ct)
        valid = [d for d, ok in zip(decs, oks) if ok]
        for d, ok in zip(decs, oks):
            if not ok:
                del slot_shares[d.decryptor_id]
                self._post(PO_HB_REJECT, a=slot, b=d.decryptor_id)
                self._flag_invalid(d.decryptor_id, slot)
        if len(valid) < need:
            return  # byzantine shares pruned; wait for more
        self._resolve(slot, self._pub.tpke_pub.full_decrypt(ct, valid))

    def _flag_invalid(self, sender: int, slot: int) -> None:
        # honey_badger.py::_flag_invalid mirror (same record coordinates)
        ev = getattr(self.router, "evidence", None)
        if ev is not None:
            ev.record_invalid_share(self.id.era, sender, "dec", (slot,))

    # -- completion (XO_HB_DONE) ----------------------------------------------
    def finish(self) -> dict:
        self.done = True
        self.result = {
            slot: pt
            for slot, pt in sorted(self._plaintexts.items())
            if pt is not None
        }
        return self.result


class RootHost:
    """Crypto half of a native RootProtocol (root_protocol.py oracle): tx
    batch assembly, header build + ECDSA sign/verify, block production."""

    def __init__(self, router, era: int, producer, ecdsa_priv, ecdsa_pubs):
        self.router = router
        self.id = M.RootProtocolId(era=era)
        self._producer = producer
        self._priv = ecdsa_priv
        self._pubs = ecdsa_pubs
        self._header = None
        self._header_hash = None
        self._txs = None
        self._signatures: Dict[int, bytes] = {}

    # XO_ROOT_INPUT — root_protocol.py::handle_input HB half (the engine
    # requests the nonce coin right after this crossing returns)
    def on_input(self) -> None:
        from ..core.block_producer import encode_tx_batch

        proposal = self._producer.get_transactions_to_propose()
        self.router.hb_host(self.id.era).handle_input(
            encode_tx_batch(proposal)
        )

    # XO_ROOT_SIGN — root_protocol.py::_try_sign_header
    def on_sign(self, parity: int) -> None:
        from ..core.block_producer import decode_tx_batch

        hb_result = self.router.hb_host(self.id.era).result or {}
        nonce = (self.id.era << 1) | (1 if parity else 0)
        seen = set()
        txs = []
        for slot in sorted(hb_result):
            try:
                batch = decode_tx_batch(hb_result[slot])
            except (ValueError, AssertionError):
                continue  # malformed proposal: skip the slot
            for stx in batch:
                h = stx.hash()
                if h not in seen:
                    seen.add(h)
                    txs.append(stx)
        self._txs = txs
        # tx lifecycle decide stamp — same point as the Python oracle's
        # _try_sign_header union (sampled-only, first stamp wins)
        from ..utils import txtrace

        txtrace.stamp_many(
            (stx.hash() for stx in txs), "decide", era=self.id.era
        )
        self._header = self._producer.create_header(self.id.era, txs, nonce)
        self._header_hash = self._header.hash()
        sig = ecdsa.sign_hash(self._priv, self._header_hash)
        payload = M.SignedHeaderMessage(
            root=self.id, header_bytes=self._header.encode(), signature=sig
        )
        wire = self.router._native_send(payload)
        self._signatures[self.router.my_id] = sig
        # two segments: the FRESH bytes drive header matching (the oracle
        # compares against self._header.encode()), the wire bytes — possibly
        # journal-substituted recorded bytes — are what actually broadcasts
        own = (
            len(payload.header_bytes).to_bytes(4, "big")
            + payload.header_bytes
            + payload.signature
        )
        bcast = (
            len(wire.header_bytes).to_bytes(4, "big")
            + wire.header_bytes
            + wire.signature
        )
        self.router._net._rt_post(
            self.router.my_id,
            PO_ROOT_HEADER,
            0,
            0,
            len(own).to_bytes(4, "big") + own + bcast,
            era=self.id.era,
        )

    # XO_ROOT_VERIFY — root_protocol.py::_on_signed_header signature checks
    def on_verify(self, blob: bytes) -> None:
        me = self.router.my_id
        era = self.id.era
        for sender, sig in iter_pairs(blob):
            if ecdsa.verify_hash(self._pubs[sender], self._header_hash, sig):
                self._signatures[sender] = sig
                self.router._net._rt_post(
                    me, PO_ROOT_ACCEPT, sender, 0, b"", era=era
                )
            else:
                self.router._net._rt_post(
                    me, PO_ROOT_REJECT, sender, 0, b"", era=era
                )
                # root_protocol.py::_on_signed_header ECDSA-reject mirror
                ev = getattr(self.router, "evidence", None)
                if ev is not None:
                    ev.record_invalid_share(era, sender, "hdr", ())

    # XO_ROOT_PRODUCE — root_protocol.py::_try_produce
    def on_produce(self):
        from ..core.types import MultiSig

        multisig = MultiSig(
            signatures=tuple(sorted(self._signatures.items()))
        )
        block = self._producer.produce_block(self._header, self._txs, multisig)
        self.router._native_results[self.id] = block
        # top-level completion: break the engine out of its chunk, exactly
        # like internal_response(to_id=None) does for Python protocols
        self.router._net._request_stop(era=self.id.era)
        return block


class RbcHost:
    """RS + Merkle half of the native ReliableBroadcast (version 7 boundary
    op). The engine keeps the full Bracha message state machine (VAL/ECHO/
    READY dedupe, thresholds, delivery) and crosses out only the codec work:
    XO_RBC_ENCODE for the sender-side shard fan-out, XO_RBC_NEED for the
    interpolate + re-encode + root-recheck verdict. Both run through the
    era RBC batcher (rbc_batcher.py) when one is wired on, so every
    validator's pending codec work in an era fuses into one batched matrix
    product — and the per-(root, k, n) verdict memo collapses the N
    in-process validators' identical interpolations into one."""

    def __init__(self, router, era: int):
        self.router = router
        self.era = era
        self.me = router.my_id
        self.n = router.n_validators
        self.f = router.f
        self.k = max(self.n - 2 * self.f, 1)

    @property
    def _batcher(self):
        return self.router.rbc_batcher

    # XO_RBC_ENCODE — reliable_broadcast.py::handle_input codec half
    def on_encode(self, slot: int, value: bytes) -> None:
        batcher = self._batcher
        if batcher is not None:
            batcher.submit_encode(
                self.era,
                value,
                self.k,
                self.n,
                lambda shards, _slot=slot: self._post_vals(_slot, shards),
            )
            return
        from ..ops import rs

        self._post_vals(slot, rs.encode(value, self.k, self.n))

    def _post_vals(self, slot: int, shards) -> None:
        from ..crypto import hashes

        # one tree a proposal, all N branches read from it: `hashes` is the
        # keccak digests it took, the leaves' and the inner nodes'
        with tracing.span(
            "rbc.merkle", "engine", era=self.era, leaves=len(shards)
        ) as sid:
            tree = hashes.merkle_tree(hashes.keccak256_batch(shards))
            tracing.annotate(sid, hashes=len(shards) + tree.hashes)
        blob = bytearray(self.era.to_bytes(4, "big"))
        blob += tree.root
        blob += self.n.to_bytes(4, "big")
        for i, branch in enumerate(tree.branches):
            blob += len(branch).to_bytes(4, "big")
            for h in branch:
                blob += len(h).to_bytes(4, "big")
                blob += h
            blob += len(shards[i]).to_bytes(4, "big")
            blob += shards[i]
        self.router._net._rt_post(
            self.me, PO_RBC_VALS, slot, 0, bytes(blob), era=self.era
        )

    # XO_RBC_NEED — reliable_broadcast.py::_try_interpolate codec half
    def on_need(self, slot: int, blob: bytes) -> None:
        root = blob[:32]
        full = [None] * self.n
        for idx, shard in iter_pairs(blob[32:]):
            if 0 <= idx < self.n:
                full[idx] = shard
        batcher = self._batcher
        if batcher is not None:
            batcher.submit_interpolate(
                self.era,
                full,
                self.k,
                self.n,
                root,
                lambda payload, _slot=slot, _root=root: self._post_result(
                    _slot, _root, payload
                ),
            )
            return
        from .rbc_batcher import scalar_verdict

        self._post_result(slot, root, scalar_verdict(full, self.k, root))

    def _post_result(self, slot: int, root: bytes, payload) -> None:
        ok = 1 if payload is not None else 0
        blob = self.era.to_bytes(4, "big") + root + (payload or b"")
        self.router._net._rt_post(
            self.me, PO_RBC_RESULT, slot, ok, blob, era=self.era
        )
