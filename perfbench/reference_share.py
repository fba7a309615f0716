"""The plain reference of the `share` driver. It knows no engine and no
batcher: from the ciphertexts the committee carried and the dealer's master
secrets (interpolated at 0 from the key shares the harness dealt), it gives

* each slot's plaintext, decrypting with the master TPKE secret directly;
* every coin value, signing the coin's id with the master
  threshold-signature secret directly, never from shares;
* each era's expected block: the slots' plaintext batches in slot order,
  deduplicated as RootHost does, in the canonical execution order
  (sender, nonce, hash), with the nonce (era << 1 | the nonce coin).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple


def master_secret(shares: Sequence[int], t: int) -> int:
    """f(0) of the degree-t polynomial whose value at i + 1 is shares[i]."""
    from lachain_tpu.crypto import bls12381 as bls

    xs = list(range(1, t + 2))
    lag = bls.fr_lagrange_coeffs(xs, at=0)
    return sum(c * s for c, s in zip(lag, shares[: t + 1])) % bls.R


@dataclass
class Expected:
    txs: list  # SignedTransaction, in execution order
    plaintexts: Dict[int, bytes]


class ShareReference:
    def __init__(self, private_keys, t: int, chain_id: int):
        from lachain_tpu.crypto.native_backend import NativeBackend

        self._host = NativeBackend()
        self.tpke_x = master_secret([p.tpke_priv.x_i for p in private_keys], t)
        self.ts_x = master_secret([p.ts_share.x_i for p in private_keys], t)
        self.chain_id = chain_id

    def plaintext(self, ciphertext: bytes) -> bytes:
        from lachain_tpu.crypto import tpke

        ct = tpke.EncryptedShare.from_bytes(ciphertext)
        return tpke.decrypt_with_combined(ct, self._host.g1_mul(ct.u, self.tpke_x))

    def coin(self, era: int, agreement: int, epoch: int) -> bool:
        from lachain_tpu.consensus import messages as M
        from lachain_tpu.crypto import threshold_sig as ts

        msg = M.CoinId(era=era, agreement=agreement, epoch=epoch).to_bytes()
        h = ts._hash_to_sig_point(msg)
        return ts.Signature(self._host.g2_mul(h, self.ts_x)).parity

    def block(self, era: int, ciphertexts: Dict[int, bytes]) -> Expected:
        from lachain_tpu.core.types import SignedTransaction
        from lachain_tpu.crypto import ecdsa
        from lachain_tpu.utils.serialization import Reader

        plaintexts = {s: self.plaintext(ciphertexts[s]) for s in sorted(ciphertexts)}
        seen, txs = set(), []
        for slot in sorted(plaintexts):
            for raw in Reader(plaintexts[slot]).bytes_list():
                stx = SignedTransaction.decode(raw)
                if stx.hash() not in seen:
                    seen.add(stx.hash())
                    txs.append(stx)
        senders = ecdsa.recover_address_batch(
            [s.tx.signing_hash(self.chain_id) for s in txs], [s.signature for s in txs]
        )
        order = sorted(
            range(len(txs)),
            key=lambda i: (senders[i] or b"\xff" * 20, txs[i].tx.nonce, txs[i].hash()),
        )
        return Expected([txs[i] for i in order], plaintexts)

    def compare(
        self,
        era: int,
        block,
        want: Expected,
        coins: Dict[Tuple[int, int], bool],
    ) -> List[str]:
        wrong = []
        if tuple(block.tx_hashes) != tuple(s.hash() for s in want.txs):
            wrong.append(f"era {era}: the block's transactions differ from the reference's")
        if block.header.nonce != (era << 1) | self.coin(era, -1, 0):
            wrong.append(f"era {era}: the header's nonce differs from the reference coin")
        if (-1, 0) not in coins:
            wrong.append(f"era {era}: validator 0 used no nonce coin")
        for (agreement, epoch), value in sorted(coins.items()):
            if value != self.coin(era, agreement, epoch):
                wrong.append(f"era {era}: coin {(agreement, epoch)} differs from the reference")
        return wrong


def multisig_failures(block, pubs: Sequence[bytes], quorum: int) -> List[str]:
    """At least `quorum` valid signatures of distinct validators over the
    header, by plain ecdsa.verify_hash."""
    from lachain_tpu.crypto import ecdsa

    digest = block.header.hash()
    signers = {
        i
        for i, sig in block.multisig.signatures
        if 0 <= i < len(pubs) and ecdsa.verify_hash(pubs[i], digest, sig)
    }
    if len(signers) < quorum:
        return [f"height {block.header.index}: {len(signers)} valid signatures, under {quorum}"]
    return []
