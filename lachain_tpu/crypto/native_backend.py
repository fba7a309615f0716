"""ctypes binding for libbls381 (the native C++ BLS12-381 backend).

Builds on demand (make in lachain_tpu/crypto/native) and exposes the same
backend interface as PythonBackend (lachain_tpu.crypto.provider). Points cross
the boundary in the shared wire format (BE uncompressed; see bls12381.py),
internally converting to/from the oracle's tuple representation so the rest of
the Python stack is backend-agnostic.

Role parity: the MCL native binding in the reference
(/root/reference/src/Lachain.Crypto/MclBls12381.cs).
"""
from __future__ import annotations

import ctypes
import os
from typing import List, Sequence, Tuple

from . import bls12381 as bls
from ..utils.native_build import ensure_built

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "native")


def load_lib():
    # LACHAIN_BLS_LIB loads an alternate backend build verbatim (the
    # ASan/TSan gates in tests/native/ point it at instrumented builds) —
    # no rebuild, same contract as LACHAIN_LSM_LIB in storage/lsm.py
    lib_path = os.environ.get("LACHAIN_BLS_LIB") or ensure_built(
        _NATIVE_DIR, "libbls381.so"
    )
    lib = ctypes.CDLL(lib_path)
    lib.lt_version.restype = ctypes.c_int
    assert lib.lt_version() == 1
    lib.lt_have_adx.restype = ctypes.c_int
    lib.lt_have_adx.argtypes = []
    return lib


def _scalar32(s: int) -> bytes:
    return (s % bls.R).to_bytes(32, "big")


class NativeBackend:
    """Backend implementation delegating hot ops to libbls381."""

    name = "native"

    def __init__(self):
        self._lib = load_lib()

    def tpke_era_verify_combine(self, jobs, verification_keys, rng=None):
        """Whole-tick TPKE verify+combine over the C++ group ops (one grand
        multi-pairing); same contract as the TPU backend's kernel version."""
        import secrets as _secrets

        from . import tpke

        return tpke.era_verify_combine_host(
            jobs, verification_keys, backend=self, rng=rng or _secrets
        )

    # -- group ops -----------------------------------------------------------
    def g1_mul(self, point: tuple, scalar: int) -> tuple:
        out = ctypes.create_string_buffer(96)
        rc = self._lib.lt_g1_mul(
            bls.g1_to_bytes(point), _scalar32(scalar), out
        )
        if rc != 0:
            raise ValueError("native g1_mul failed")
        return bls.g1_from_bytes(out.raw, check_subgroup=False)

    def g1_mul_batch(
        self, points: Sequence[tuple], scalars: Sequence[int]
    ) -> List[tuple]:
        """n independent muls in one threaded native call (NOT an MSM — no
        accumulation). The TPKE decrypt-share shape: 64 slots x one
        U^{x_i} each per era tick."""
        if len(points) != len(scalars):
            raise ValueError("g1_mul_batch: length mismatch")
        if not points:
            return []
        pts = b"".join(bls.g1_to_bytes(p) for p in points)
        ss = b"".join(_scalar32(s) for s in scalars)
        out = ctypes.create_string_buffer(96 * len(points))
        nt = min(os.cpu_count() or 1, 16)
        rc = self._lib.lt_g1_mul_batch(pts, ss, len(points), nt, out)
        if rc != 0:
            raise ValueError("native g1_mul_batch failed")
        return [
            bls.g1_from_bytes(
                out.raw[i * 96 : (i + 1) * 96], check_subgroup=False
            )
            for i in range(len(points))
        ]

    def mul_fixed_base(
        self, base: bytes, scalars: Sequence[int], threads: int = 0
    ) -> List[bytes]:
        """Serialized `base * s` for each s, one wire point (G1: 96 bytes,
        G2: 192) against many scalars: every share of one threshold key
        over a common point in one native call, on `threads` threads (0:
        the host's cores, at most 16)."""
        width = len(base)
        fn = {96: self._lib.lt_g1_mul_fixed, 192: self._lib.lt_g2_mul_fixed}[width]
        out = ctypes.create_string_buffer(width * len(scalars))
        ss = b"".join(_scalar32(s) for s in scalars)
        nt = threads or min(os.cpu_count() or 1, 16)
        if fn(base, ss, len(scalars), nt, out) != 0:
            raise ValueError("native mul_fixed_base: bad point encoding")
        raw = out.raw
        return [raw[i * width : (i + 1) * width] for i in range(len(scalars))]

    def g2_mul(self, point: tuple, scalar: int) -> tuple:
        out = ctypes.create_string_buffer(192)
        rc = self._lib.lt_g2_mul(
            bls.g2_to_bytes(point), _scalar32(scalar), out
        )
        if rc != 0:
            raise ValueError("native g2_mul failed")
        return bls.g2_from_bytes(out.raw, check_subgroup=False)

    def g1_msm(self, points: Sequence[tuple], scalars: Sequence[int]) -> tuple:
        if len(points) != len(scalars):
            raise ValueError("g1_msm: points/scalars length mismatch")
        if not points:
            return bls.G1_INF
        pts = b"".join(bls.g1_to_bytes(p) for p in points)
        ss = b"".join(_scalar32(s) for s in scalars)
        out = ctypes.create_string_buffer(96)
        rc = self._lib.lt_g1_msm(pts, ss, len(points), out)
        if rc != 0:
            raise ValueError("native g1_msm failed")
        return bls.g1_from_bytes(out.raw, check_subgroup=False)

    def g2_msm(self, points: Sequence[tuple], scalars: Sequence[int]) -> tuple:
        if len(points) != len(scalars):
            raise ValueError("g2_msm: points/scalars length mismatch")
        if not points:
            return bls.G2_INF
        pts = b"".join(bls.g2_to_bytes(p) for p in points)
        ss = b"".join(_scalar32(s) for s in scalars)
        out = ctypes.create_string_buffer(192)
        rc = self._lib.lt_g2_msm(pts, ss, len(points), out)
        if rc != 0:
            raise ValueError("native g2_msm failed")
        return bls.g2_from_bytes(out.raw, check_subgroup=False)

    # -- pairings ------------------------------------------------------------
    def pairing_check(self, pairs: Sequence[Tuple[tuple, tuple]]) -> bool:
        """Prod e(P_i, Q_i) == 1. Large products (the era-sized grand check,
        2S pairs) spread their independent Miller loops across threads with
        one shared final exponentiation; small ones stay serial (thread
        spawn would dominate)."""
        if not pairs:
            return True
        g1s = b"".join(bls.g1_to_bytes(p) for p, _ in pairs)
        g2s = b"".join(bls.g2_to_bytes(q) for _, q in pairs)
        if len(pairs) >= 8:
            nt = min(os.cpu_count() or 1, 16)
            rc = self._lib.lt_pairing_check_mt(g1s, g2s, len(pairs), nt)
        else:
            rc = self._lib.lt_pairing_check(g1s, g2s, len(pairs))
        if rc < 0:
            raise ValueError("native pairing_check: bad encoding")
        return rc == 1

    def pairings_equal(self, p_a, q_a, p_b, q_b) -> bool:
        return self.pairing_check([(p_a, q_a), (bls.g1_neg(p_b), q_b)])

    def multi_pairing_bytes(
        self, pairs: Sequence[Tuple[tuple, tuple]]
    ) -> bytes:
        """GT output serialized — for conformance tests vs the oracle."""
        g1s = b"".join(bls.g1_to_bytes(p) for p, _ in pairs)
        g2s = b"".join(bls.g2_to_bytes(q) for _, q in pairs)
        out = ctypes.create_string_buffer(576)
        rc = self._lib.lt_multi_pairing(g1s, g2s, len(pairs), out)
        if rc != 0:
            raise ValueError("native multi_pairing failed")
        return out.raw

    # -- hashing -------------------------------------------------------------
    def hash_to_g1(self, msg: bytes, domain: bytes = b"LTPU-G1") -> tuple:
        out = ctypes.create_string_buffer(96)
        self._lib.lt_hash_to_g1(msg, len(msg), domain, len(domain), out)
        return bls.g1_from_bytes(out.raw, check_subgroup=False)

    def hash_to_g2(self, msg: bytes, domain: bytes = b"LTPU-G2") -> tuple:
        out = ctypes.create_string_buffer(192)
        self._lib.lt_hash_to_g2(msg, len(msg), domain, len(domain), out)
        return bls.g2_from_bytes(out.raw, check_subgroup=False)

    # -- wire deserialization (native on-curve + subgroup check) -------------
    def g1_deserialize(self, data: bytes) -> tuple:
        if len(data) != bls.G1_BYTES:
            raise ValueError("bad G1 encoding length")
        if self._lib.lt_g1_check(data) != 2:
            raise ValueError("G1 point invalid or not in subgroup")
        return bls.g1_from_bytes(data, check_subgroup=False)

    def g2_deserialize(self, data: bytes) -> tuple:
        if len(data) != bls.G2_BYTES:
            raise ValueError("bad G2 encoding length")
        if self._lib.lt_g2_check(data) != 2:
            raise ValueError("G2 point invalid or not in subgroup")
        return bls.g2_from_bytes(data, check_subgroup=False)

    def keccak256(self, data: bytes) -> bytes:
        out = ctypes.create_string_buffer(32)
        self._lib.lt_keccak256(data, len(data), out)
        return out.raw

    # -- baseline proxy ------------------------------------------------------
    def tpke_verify_shares_serial(
        self,
        uis: Sequence[tuple],
        yis: Sequence[tuple],
        h: tuple,
        w: tuple,
    ) -> List[bool]:
        """Reference-style serial loop: 2 pairings per share (the baseline
        the batched TPU path is measured against — BASELINE.md)."""
        n = len(uis)
        ub = b"".join(bls.g1_to_bytes(u) for u in uis)
        yb = b"".join(bls.g1_to_bytes(y) for y in yis)
        res = ctypes.create_string_buffer(n)
        rc = self._lib.lt_tpke_verify_shares_serial(
            ub, yb, n, bls.g2_to_bytes(h), bls.g2_to_bytes(w), res
        )
        if rc != 0:
            raise ValueError("native serial verify failed")
        return [b == 1 for b in res.raw]
