"""BLS threshold signatures (signatures in G2, public keys in G1).

Functional parity with the reference's threshold-signature layer
(/root/reference/src/Lachain.Crypto/ThresholdSignature/):
  * PrivateKeyShare.HashAndSign   (PrivateKeyShare.cs:20-27) -> sign()
  * PublicKey.ValidateSignature   (PublicKey.cs:15-20)       -> verify()
  * PublicKeySet.AssembleSignature(PublicKeySet.cs:35-44)    -> combine()
  * ThresholdSigner.AddShare      (ThresholdSigner.cs:45-90) -> ThresholdSigner
  * Signature.Parity              (Signature.cs:20-24)       -> Signature.parity
  * TrustedKeyGen                 (TrustedKeyGen.cs:8-35)    -> TsTrustedKeyGen

Scheme:
  keys    : x = f(0), degree-t polynomial; validator i holds x_i = f(i+1);
            shared pk Y = g1^x, per-validator pk Y_i = g1^{x_i}.
  sign    : sigma_i = H_G2(msg)^{x_i}.
  verify  : e(g1, sigma_i) == e(Y_i, H_G2(msg)).
  combine : sigma = Lagrange_0({(i+1, sigma_i)}) in G2; verify against Y.

TPU-first batch verification (`batch_verify_shares`): random linear
combination collapses M share checks into 2 pairings + one G1 MSM + one G2
MSM — the per-coin hot path in CommonCoin (reference CommonCoin.cs:75-96
verifies every share with 2 pairings, serially).
"""
from __future__ import annotations

import secrets
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

from . import bls12381 as bls
from ..utils import metrics
from .hashes import keccak256
from .provider import batch_bisect_verify, get_backend, select_distinct

_SIG_DOMAIN = b"LTPU-TSIG"


import functools


@functools.lru_cache(maxsize=4096)
def _hash_to_sig_point(msg: bytes) -> tuple:
    """Memoized: every sign/verify/combine of one coin re-hashes the same
    coin id (N+1 times per coin per validator at N=64)."""
    return get_backend().hash_to_g2(msg, _SIG_DOMAIN)


@dataclass(frozen=True)
class Signature:
    """Combined or partial signature (a G2 point)."""

    sigma: tuple

    def to_bytes(self) -> bytes:
        return bls.g2_to_bytes(self.sigma)

    @classmethod
    def from_bytes(cls, data: bytes) -> "Signature":
        return cls(get_backend().g2_deserialize(data))

    @property
    def parity(self) -> bool:
        """Deterministic coin bit (role of Signature.Parity in the reference,
        Signature.cs:20-24; we take the low bit of keccak256 of the
        serialized point — any fixed extractor works, all correct nodes
        compute the same combined sigma)."""
        return bool(keccak256(self.to_bytes())[0] & 1)


@dataclass(frozen=True)
class PartialSignature:
    sigma: tuple  # G2
    signer_id: int

    def to_bytes(self) -> bytes:
        from ..utils.serialization import write_u32

        return bls.g2_to_bytes(self.sigma) + write_u32(self.signer_id)

    @classmethod
    def from_bytes(cls, data: bytes) -> "PartialSignature":
        from ..utils.serialization import Reader

        sigma = get_backend().g2_deserialize(data[: bls.G2_BYTES])
        r = Reader(data[bls.G2_BYTES :])
        signer = r.u32()
        r.assert_eof()
        return cls(sigma, signer)


class TsPublicKey:
    """Single public key (shared or per-validator), in G1."""

    def __init__(self, y: tuple):
        self.y = y

    def to_bytes(self) -> bytes:
        return bls.g1_to_bytes(self.y)

    @classmethod
    def from_bytes(cls, data: bytes) -> "TsPublicKey":
        return cls(get_backend().g1_deserialize(data))

    @metrics.timed("crypto_ts_verify")
    def verify(self, msg: bytes, sig: Signature) -> bool:
        """e(g1, sigma) == e(Y, H_G2(msg))
        (reference: ThresholdSignature/PublicKey.cs:15-20)."""
        h = _hash_to_sig_point(msg)
        return get_backend().pairing_check(
            [(bls.G1_GEN, sig.sigma), (bls.g1_neg(self.y), h)]
        )


class TsPublicKeySet:
    """All validators' public keys + threshold
    (reference: ThresholdSignature/PublicKeySet.cs)."""

    def __init__(self, keys: Sequence[TsPublicKey], t: int):
        self.keys = list(keys)
        self.t = t  # t+1 shares assemble a signature
        # shared key = interpolation of the per-validator keys at 0
        xs = list(range(1, len(self.keys) + 1))
        self.shared = TsPublicKey(
            bls.g1_interpolate(xs[: t + 1], [k.y for k in self.keys[: t + 1]])
        )

    def to_bytes(self) -> bytes:
        from ..utils.serialization import write_bytes_list, write_u32

        return write_u32(self.t) + write_bytes_list(
            [k.to_bytes() for k in self.keys]
        )

    @classmethod
    def from_bytes(cls, data: bytes) -> "TsPublicKeySet":
        from ..utils.serialization import Reader

        r = Reader(data)
        t = r.u32()
        keys = [TsPublicKey.from_bytes(b) for b in r.bytes_list()]
        r.assert_eof()
        return cls(keys, t)

    @property
    def n(self) -> int:
        return len(self.keys)

    @metrics.timed("crypto_ts_verify_share")
    def verify_share(self, msg: bytes, ps: PartialSignature) -> bool:
        """e(g1, sigma_i) == e(Y_i, H(msg)) — per-share hot op
        (reference: ThresholdSigner.cs:92-95)."""
        if not (0 <= ps.signer_id < len(self.keys)):
            return False
        h = _hash_to_sig_point(msg)
        yk = self.keys[ps.signer_id].y
        return get_backend().pairing_check(
            [(bls.G1_GEN, ps.sigma), (bls.g1_neg(yk), h)]
        )

    def batch_verify_shares(
        self,
        msg: bytes,
        shares: Sequence[PartialSignature],
        rng=secrets,
    ) -> List[bool]:
        """Random-linear-combination batch check (TPU-first redesign):
          e(g1, sum c_i sigma_i) == e(sum c_i Y_i, H(msg))
        2 pairings + 1 G2 MSM + 1 G1 MSM for the whole batch; bisect on
        failure to isolate bad shares."""
        if not shares:
            return []
        in_range = [0 <= s.signer_id < len(self.keys) for s in shares]
        live = [i for i, ok in enumerate(in_range) if ok]
        if not live:
            return [False] * len(shares)
        h = _hash_to_sig_point(msg)
        backend = get_backend()

        def group_ok(idx: List[int]) -> bool:
            # < 2^128 so the TPU path's 128-bit encoding is exact
            cs = [rng.randbelow((1 << 128) - 1) + 1 for _ in idx]
            sig_agg = backend.g2_msm(
                [shares[live[i]].sigma for i in idx], cs
            )
            y_agg = backend.g1_msm(
                [self.keys[shares[live[i]].signer_id].y for i in idx], cs
            )
            return backend.pairing_check(
                [(bls.G1_GEN, sig_agg), (bls.g1_neg(y_agg), h)]
            )

        live_results = batch_bisect_verify(group_ok, len(live))
        results = [False] * len(shares)
        for pos, i in enumerate(live):
            results[i] = live_results[pos]
        return results

    @metrics.timed("crypto_ts_combine")
    def combine(self, shares: Sequence[PartialSignature]) -> Signature:
        """Lagrange-assemble t+1 partial signatures in G2
        (reference: PublicKeySet.cs:35-44)."""
        chosen = select_distinct(
            shares, key=lambda s: s.signer_id, count=self.t + 1
        )
        if chosen is None:
            raise ValueError(
                f"need {self.t + 1} distinct signer ids, got "
                f"{len(set(s.signer_id for s in shares))}"
            )
        shares = chosen
        xs = [s.signer_id + 1 for s in shares]
        cs = bls.fr_lagrange_coeffs(xs, at=0)
        sigma = get_backend().g2_msm([s.sigma for s in shares], cs)
        return Signature(sigma)


def era_verify_combine(
    key_set: TsPublicKeySet,
    coins,
    rng=secrets,
):
    """Era-tick batch: verify + combine MANY coins' shares at once.

    coins: list of (msg: bytes, shares: Dict[int, PartialSignature]) — one
    entry per pending coin, shares keyed by signer id (>= t+1 each).
    Returns a list of Optional[Signature] (None where a coin's batch
    contained an invalid share — callers fall back to the per-share path
    to prune it, mirroring ThresholdSigner.add_share).

    With the `tpu` backend this rides the Pallas G2 era kernel
    (ops/pg2.py) behind `ts_era_verify_combine` — S x K lanes, one grand
    multi-pairing; elsewhere it degrades to the same per-coin host ops
    TsPublicKeySet.batch_verify_shares/combine use. Reference semantics:
    ThresholdSigner.cs:45-95 + PublicKeySet.cs:35-44, serial there.
    """
    # both paths verify exactly the chosen (lowest-signer-id) t+1 shares —
    # the ones the combine consumes — so the device and host backends agree
    # on every input (an unchosen invalid share can never flip the result);
    # coins without t+1 in-range signers resolve to None without any work
    out: List[Optional[Signature]] = [None] * len(coins)
    live: List[int] = []
    chosen_per_coin: List[list] = []
    for idx, (_msg, shares) in enumerate(coins):
        valid_ids = sorted(i for i in shares if 0 <= i < key_set.n)
        if len(valid_ids) > key_set.t:
            live.append(idx)
            chosen_per_coin.append(valid_ids[: key_set.t + 1])

    def host_path():
        for idx, signers in zip(live, chosen_per_coin):
            msg, shares = coins[idx]
            chosen = [shares[i] for i in signers]
            oks = key_set.batch_verify_shares(msg, chosen, rng=rng)
            out[idx] = key_set.combine(chosen) if all(oks) else None
        return out

    backend = get_backend()
    era_fn = getattr(backend, "ts_era_verify_combine", None)
    if era_fn is None or not live:
        return host_path()
    from .tpu_backend import CoinJob

    jobs = []
    for idx, signers in zip(live, chosen_per_coin):
        msg, shares = coins[idx]
        cs = bls.fr_lagrange_coeffs([i + 1 for i in signers], at=0)
        lag_row = [0] * key_set.n
        sigma_row = [None] * key_set.n
        for i, c in zip(signers, cs):
            lag_row[i] = c
            sigma_row[i] = shares[i].sigma
        jobs.append(
            CoinJob(
                sigma_by_signer=sigma_row,
                lagrange_row=lag_row,
                h=_hash_to_sig_point(msg),
            )
        )
    results = era_fn(jobs, key_set.keys, rng=rng)
    for idx, (ok, comb) in zip(live, results):
        out[idx] = Signature(comb) if ok else None
    return out


class TsPrivateKeyShare:
    """Validator signing share x_i
    (reference: ThresholdSignature/PrivateKeyShare.cs)."""

    def __init__(self, x_i: int, my_id: int):
        self.x_i = x_i % bls.R
        self.my_id = my_id

    def to_bytes(self) -> bytes:
        from ..utils.serialization import write_u32

        return bls.fr_to_bytes(self.x_i) + write_u32(self.my_id)

    @classmethod
    def from_bytes(cls, data: bytes) -> "TsPrivateKeyShare":
        from ..utils.serialization import Reader

        x = bls.fr_from_bytes(data[: bls.FR_BYTES])
        r = Reader(data[bls.FR_BYTES :])
        my_id = r.u32()
        r.assert_eof()
        return cls(x, my_id)

    def public_key(self) -> TsPublicKey:
        return TsPublicKey(bls.g1_mul(bls.G1_GEN, self.x_i))

    @metrics.timed("crypto_ts_sign")
    def sign(self, msg: bytes) -> PartialSignature:
        """sigma_i = H_G2(msg)^{x_i}
        (reference: PrivateKeyShare.cs:20-27 HashAndSign)."""
        h = _hash_to_sig_point(msg)
        return PartialSignature(
            sigma=get_backend().g2_mul(h, self.x_i), signer_id=self.my_id
        )


class ThresholdSigner:
    """Stateful per-message share collector
    (reference: ThresholdSignature/ThresholdSigner.cs:45-90 and the
    IThresholdSigner seam named in SURVEY.md §1).

    Collects shares, verifies each (single or deferred-batch), and produces
    the combined signature once t+1 valid shares are present.
    """

    def __init__(
        self,
        msg: bytes,
        key_share: TsPrivateKeyShare,
        pub_key_set: TsPublicKeySet,
    ):
        self.msg = msg
        self.key_share = key_share
        self.pub_key_set = pub_key_set
        self._shares: Dict[int, PartialSignature] = {}
        self._signature: Optional[Signature] = None
        # signer ids whose shares failed the deferred batch verification —
        # Byzantine evidence the owning protocol surfaces (evidence.py)
        self.pruned: set = set()

    def sign(self) -> PartialSignature:
        return self.key_share.sign(self.msg)

    def add_share(self, ps: PartialSignature, verify: bool = True) -> bool:
        """Returns True if the share was accepted. Combined signature becomes
        available once t+1 distinct valid shares are collected."""
        if self._signature is not None:
            return True  # already done
        if ps.signer_id in self._shares:
            return self._shares[ps.signer_id].sigma == ps.sigma
        if not (0 <= ps.signer_id < self.pub_key_set.n):
            return False
        if verify and not self.pub_key_set.verify_share(self.msg, ps):
            return False
        self._shares[ps.signer_id] = ps
        if len(self._shares) >= self.pub_key_set.t + 1:
            sig = self.pub_key_set.combine(list(self._shares.values()))
            if self.pub_key_set.shared.verify(self.msg, sig):
                self._signature = sig
            else:
                # A bad share slipped in (deferred-verification mode): prune
                # invalid shares so they cannot poison every later combine.
                held = list(self._shares.values())
                oks = self.pub_key_set.batch_verify_shares(self.msg, held)
                self.pruned.update(
                    s.signer_id for s, ok in zip(held, oks) if not ok
                )
                self._shares = {
                    s.signer_id: s for s, ok in zip(held, oks) if ok
                }
                if len(self._shares) >= self.pub_key_set.t + 1:
                    sig = self.pub_key_set.combine(list(self._shares.values()))
                    if self.pub_key_set.shared.verify(self.msg, sig):
                        self._signature = sig
        return True

    @property
    def signature(self) -> Optional[Signature]:
        return self._signature


class TsTrustedKeyGen:
    """Trusted dealer for tests/devnets
    (reference: ThresholdSignature/TrustedKeyGen.cs:8-35)."""

    def __init__(self, n: int, f: int, rng=secrets):
        if n <= 3 * f and not (f == 0 and n >= 1):
            raise ValueError("dealer requires n > 3f")
        coeffs = [rng.randbelow(bls.R) for _ in range(f + 1)]
        self._shares = [bls.fr_eval_poly(coeffs, i + 1) for i in range(n)]
        self.pub_key_set = TsPublicKeySet(
            [
                TsPublicKey(bls.g1_mul(bls.G1_GEN, s))
                for s in self._shares
            ],
            t=f,
        )
        # dealer sanity: interpolated shared key matches g1^f(0)
        assert bls.g1_eq(
            self.pub_key_set.shared.y, bls.g1_mul(bls.G1_GEN, coeffs[0])
        )

    def private_key_share(self, i: int) -> TsPrivateKeyShare:
        return TsPrivateKeyShare(self._shares[i], i)
