"""CommonCoin: threshold signature of the coin id; coin = signature parity.

Behavioral parity with the reference
(/root/reference/src/Lachain.Consensus/CommonCoin/CommonCoin.cs):
  * on request: sign CoinId bytes with my TS share, broadcast (117-124)
  * collect + verify shares; combine at t+1 (75-96)
  * coin bit = combined signature parity (CoinResult.cs:15-19)

TPU-first note: share verification goes through ThresholdSigner, whose
deferred-batch mode routes to the RLC batch verifier (2 pairings + MSM per
pending batch) rather than 2 pairings per share.
"""
from __future__ import annotations


from ..crypto import threshold_sig as ts
from . import messages as M
from .protocol import Broadcaster, Protocol


class CommonCoin(Protocol):
    family = "coin"

    def __init__(
        self,
        pid: M.CoinId,
        broadcaster: Broadcaster,
        key_share: ts.TsPrivateKeyShare,
        pub_key_set: ts.TsPublicKeySet,
    ):
        super().__init__(pid, broadcaster)
        self._signer = ts.ThresholdSigner(pid.to_bytes(), key_share, pub_key_set)
        self._requested = False
        self._done = False
        # raw share bytes per sender, parsed lazily: only once t+1 candidates
        # exist does anyone pay the G2 parse — and then via ONE batched
        # deserialize+subgroup check instead of a full-order mul per point
        self._raw: dict = {}
        self._parsed: set = set()
        self._flagged: set = set()  # senders already reported as evidence

    def handle_input(self, value) -> None:
        if self._requested:
            return
        self._requested = True
        my_share = self._signer.sign()
        self.broadcaster.broadcast(
            M.CoinMessage(coin=self.id, share=my_share.to_bytes())
        )
        # my own share counts immediately (no parse needed — it's ours)
        self._raw[self.me] = my_share.to_bytes()
        self._parsed.add(self.me)
        self._signer.add_share(my_share, verify=False)
        self._try_combine()

    def handle_external(self, sender: int, payload) -> None:
        if not isinstance(payload, M.CoinMessage):
            raise TypeError(f"unexpected payload {type(payload)}")
        if self._done or sender in self._raw:
            return
        from ..crypto import bls12381 as bls

        data = payload.share
        # id/length checks straight off the wire; share must be the sender's
        # own (equivocation check) — point parse deferred to combine time
        if len(data) != bls.G2_BYTES + 4:
            return
        if int.from_bytes(data[bls.G2_BYTES :], "big") != sender:
            return
        self._raw[sender] = data
        self._try_combine()

    def _try_combine(self) -> None:
        if self._done:
            return
        need = self._signer.pub_key_set.t + 1
        if len(self._raw) < need:
            return
        pending = [s for s in sorted(self._raw) if s not in self._parsed]
        if pending:
            from ..crypto import bls12381 as bls
            from ..crypto.provider import deserialize_batch_g2

            pts = deserialize_batch_g2(
                [self._raw[s][: bls.G2_BYTES] for s in pending]
            )
            for s, pt in zip(pending, pts):
                self._parsed.add(s)
                if pt is None:
                    self._flag_invalid(s)
                    continue  # malformed/bad-subgroup share: drop
                # deferred verification: the signer checks the COMBINED
                # signature (2 pairings total) and only falls back to the
                # RLC batch verifier to prune bad shares when that fails
                self._signer.add_share(
                    ts.PartialSignature(sigma=pt, signer_id=s), verify=False
                )
        sig = self._signer.signature
        # shares the signer's batch verifier pruned (well-formed points
        # carrying a signature over the wrong message) are evidence too
        for s in self._signer.pruned - self._flagged:
            self._flag_invalid(s)
        if sig is not None:
            self._done = True
            self.emit_result(sig.parity)

    def _flag_invalid(self, sender: int) -> None:
        if sender in self._flagged:
            return
        self._flagged.add(sender)
        ev = getattr(self.broadcaster, "evidence", None)
        if ev is not None:
            ev.record_invalid_share(
                self.id.era, sender, "coin", (self.id.agreement, self.id.epoch)
            )
