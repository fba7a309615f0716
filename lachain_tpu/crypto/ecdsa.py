"""secp256k1 ECDSA: sign / verify / recover, RFC 6979 deterministic nonces.

Parity with the reference's ECDSA surface
(/root/reference/src/Lachain.Crypto/DefaultCrypto.cs:17-337 over
Secp256k1.Net): transaction + consensus-header signatures with public-key
recovery, 65-byte (r || s || v) signatures, Ethereum-style addresses.

Pure Python (curve ops on ints) as the oracle; the C++ backend
(crypto/native/secp256k1.cpp) gives the same bytes. The native signer is
constant time in the nonce and the private key: a fixed-base comb with
every table entry of a window read under a mask, no branch on either
secret. THE ORACLE'S SIGNING IS NOT: its double-and-add branches on every
nonce bit, so timing/cache side channels can leak nonce bits of a
frequently-signing key (lattice attacks); LACHAIN_TPU_ECDSA=python, which
forces it, is devnet-grade for signing. Verification and recovery take
only public inputs.
"""
from __future__ import annotations

import functools
import hashlib
import hmac
from typing import List, Optional, Sequence, Tuple

from .hashes import keccak256

# secp256k1 domain parameters
P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8
G = (GX, GY)


def _inv(a: int, m: int) -> int:
    return pow(a, -1, m)


def _add(p: Optional[Tuple[int, int]], q: Optional[Tuple[int, int]]):
    if p is None:
        return q
    if q is None:
        return p
    x1, y1 = p
    x2, y2 = q
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = (3 * x1 * x1) * _inv(2 * y1, P) % P
    else:
        lam = (y2 - y1) * _inv(x2 - x1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    y3 = (lam * (x1 - x3) - y1) % P
    return (x3, y3)


def _mul(p: Optional[Tuple[int, int]], k: int):
    k %= N
    result = None
    addend = p
    while k:
        if k & 1:
            result = _add(result, addend)
        addend = _add(addend, addend)
        k >>= 1
    return result


from ..utils import metrics

def generate_private_key(rng=None) -> bytes:
    import secrets as _secrets

    rng = rng or _secrets
    while True:
        k = rng.randbelow(N)
        if 1 <= k < N:
            return k.to_bytes(32, "big")


def public_key_point(priv: bytes) -> Tuple[int, int]:
    return _mul(G, int.from_bytes(priv, "big"))


# keccak(priv) -> compressed pubkey; nodes sign with a handful of
# long-lived keys and the pure-Python ladder costs ~10 ms per derivation.
# Keyed by a HASH of the private key so the cache never pins secret bytes
# in process memory beyond the caller's own copy.
_pub_cache: dict = {}


def public_key_bytes(priv: bytes) -> bytes:
    """Compressed SEC1 encoding (33 bytes)."""
    from .hashes import keccak256

    ck = keccak256(priv)
    cached = _pub_cache.get(ck)
    if cached is not None:
        return cached
    pub = None
    lib = _native_lib()
    if lib is not None:
        import ctypes as _ct

        out = (_ct.c_ubyte * 33)()
        if lib.lt_ec_pubkey(priv, out) == 0:
            pub = bytes(out)
    if pub is None:
        x, y = public_key_point(priv)
        pub = bytes([0x02 | (y & 1)]) + x.to_bytes(32, "big")
    if len(_pub_cache) > 4096:
        _pub_cache.clear()
    _pub_cache[ck] = pub
    return pub


def decompress_public_key(pub: bytes) -> Tuple[int, int]:
    # ValueError (not assert) so malformed keys from untrusted input —
    # contract crypto_verify calls, wire MessageBatch senders — are a
    # clean "invalid" on every backend: the native lt_ec_verify returns
    # false for a non-02/03 prefix, and _verify_hash_py catches ValueError.
    # An AssertionError here would trap python-backend nodes while native
    # nodes return 0, forking state across a mixed deployment.
    if len(pub) != 33 or pub[0] not in (2, 3):
        raise ValueError("pubkey must be 33 bytes with 02/03 prefix")
    x = int.from_bytes(pub[1:], "big")
    if x >= P:
        raise ValueError("pubkey x out of range")
    y2 = (pow(x, 3, P) + 7) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        raise ValueError("pubkey not on curve")
    if (y & 1) != (pub[0] & 1):
        y = P - y
    return (x, y)


def address_from_public_key(pub: bytes) -> bytes:
    """20-byte Ethereum-style address: keccak256(uncompressed_xy)[12:]."""
    x, y = decompress_public_key(pub) if len(pub) == 33 else (
        int.from_bytes(pub[1:33], "big"),
        int.from_bytes(pub[33:], "big"),
    )
    raw = x.to_bytes(32, "big") + y.to_bytes(32, "big")
    return keccak256(raw)[12:]


def _rfc6979_k(priv: bytes, msg_hash: bytes) -> int:
    """Deterministic nonce per RFC 6979 (HMAC-SHA256)."""
    holder = b"\x01" * 32
    key = b"\x00" * 32
    key = hmac.new(key, holder + b"\x00" + priv + msg_hash, hashlib.sha256).digest()
    holder = hmac.new(key, holder, hashlib.sha256).digest()
    key = hmac.new(key, holder + b"\x01" + priv + msg_hash, hashlib.sha256).digest()
    holder = hmac.new(key, holder, hashlib.sha256).digest()
    while True:
        holder = hmac.new(key, holder, hashlib.sha256).digest()
        k = int.from_bytes(holder, "big")
        if 1 <= k < N:
            return k
        key = hmac.new(key, holder + b"\x00", hashlib.sha256).digest()
        holder = hmac.new(key, holder, hashlib.sha256).digest()


_native_lib_cache = [False, None]  # [attempted, lib]


def _native_lib():
    """The C++ secp256k1 backend (lachain_tpu/crypto/native/secp256k1.cpp,
    cross-checked against this module's pure-Python oracle in
    tests/test_ecdsa.py). LACHAIN_TPU_ECDSA=python forces the oracle."""
    if not _native_lib_cache[0]:
        _native_lib_cache[0] = True
        import os as _os

        if _os.environ.get("LACHAIN_TPU_ECDSA") != "python":
            try:
                from .native_backend import load_lib

                _native_lib_cache[1] = load_lib()
            except Exception:
                # a library that fails to build is an error unless the
                # Python oracle was asked for (crypto/provider.py rule)
                if _os.environ.get("LACHAIN_TPU_BACKEND") != "python":
                    raise
    return _native_lib_cache[1]


@metrics.timed("crypto_ec_sign")
def sign_hash(priv: bytes, msg_hash: bytes) -> bytes:
    """65-byte recoverable signature r(32) || s(32) || v(1), low-s enforced."""
    assert len(msg_hash) == 32 and len(priv) == 32
    lib = _native_lib()
    if lib is not None:
        import ctypes as _ct

        out = (_ct.c_ubyte * 65)()
        if lib.lt_ec_sign(priv, msg_hash, out) == 0:
            return bytes(out)
    return _sign_hash_py(priv, msg_hash)


def _sign_hash_py(priv: bytes, msg_hash: bytes) -> bytes:
    assert len(msg_hash) == 32
    z = int.from_bytes(msg_hash, "big") % N
    d = int.from_bytes(priv, "big")
    extra = b""
    while True:
        # r == 0 / s == 0 are ~2^-256 events; retry with a tweaked nonce
        # stream while keeping z bound to the ORIGINAL message hash.
        k = _rfc6979_k(priv, hashlib.sha256(msg_hash + extra).digest() if extra else msg_hash)
        pt = _mul(G, k)
        r = pt[0] % N
        if r == 0:
            extra += b"\x00"
            continue
        s = _inv(k, N) * (z + r * d) % N
        if s == 0:
            extra += b"\x00"
            continue
        v = (pt[1] & 1) | (2 if pt[0] >= N else 0)
        if s > N // 2:  # low-s normalization flips the parity bit
            s = N - s
            v ^= 1
        return r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v])


@metrics.timed("crypto_ec_verify")
def verify_hash(pub: bytes, msg_hash: bytes, sig: bytes) -> bool:
    lib = _native_lib()
    if lib is not None and len(pub) == 33 and len(msg_hash) == 32:
        return bool(lib.lt_ec_verify(pub, msg_hash, sig, len(sig)))
    return _verify_hash_py(pub, msg_hash, sig)


def _verify_hash_py(pub: bytes, msg_hash: bytes, sig: bytes) -> bool:
    if len(sig) != 65:
        return False
    try:
        q = decompress_public_key(pub)
    except ValueError:
        return False
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:64], "big")
    if not (1 <= r < N and 1 <= s < N):
        return False
    z = int.from_bytes(msg_hash, "big") % N
    w = _inv(s, N)
    u1 = z * w % N
    u2 = r * w % N
    pt = _add(_mul(G, u1), _mul(q, u2))
    if pt is None:
        return False
    return pt[0] % N == r


def ecdh_shared_secret(priv: bytes, pub: bytes) -> bytes:
    """32-byte shared secret: sha256 of the compressed shared point
    (role of the reference's EcdhAgreement inside Secp256K1Encrypt,
    DefaultCrypto.cs:301-318)."""
    pt = _mul(decompress_public_key(pub), int.from_bytes(priv, "big"))
    if pt is None:
        raise ValueError("degenerate ECDH result")
    compressed = bytes([0x02 | (pt[1] & 1)]) + pt[0].to_bytes(32, "big")
    return hashlib.sha256(compressed).digest()


def aes_gcm_encrypt(key: bytes, plaintext: bytes) -> bytes:
    """nonce(12) || ciphertext+tag (reference: DefaultCrypto.AesGcmEncrypt,
    DefaultCrypto.cs:267-283). Falls back to the pure-Python GCM when the
    `cryptography` package is absent — same wire format either way."""
    import secrets as _secrets

    nonce = _secrets.token_bytes(12)
    try:
        from cryptography.hazmat.primitives.ciphers.aead import AESGCM
    except ImportError:
        from . import _aes_fallback

        return nonce + _aes_fallback.encrypt(key, nonce, plaintext)
    return nonce + AESGCM(key).encrypt(nonce, plaintext, None)


def aes_gcm_decrypt(key: bytes, data: bytes) -> bytes:
    if len(data) < 12 + 16:
        raise ValueError("AES-GCM payload too short")
    try:
        from cryptography.hazmat.primitives.ciphers.aead import AESGCM
    except ImportError:
        from . import _aes_fallback

        return _aes_fallback.decrypt(key, data[:12], data[12:])
    return AESGCM(key).decrypt(data[:12], data[12:], None)


def ecies_encrypt(pub: bytes, plaintext: bytes, rng=None) -> bytes:
    """ECIES = ephemeral ECDH + AES-GCM
    (reference: DefaultCrypto.Secp256K1Encrypt, DefaultCrypto.cs:301-318).
    Layout: ephemeral compressed pubkey (33) || nonce (12) || ct+tag."""
    eph = generate_private_key(rng)
    key = ecdh_shared_secret(eph, pub)
    return public_key_bytes(eph) + aes_gcm_encrypt(key, plaintext)


def ecies_decrypt(priv: bytes, data: bytes) -> bytes:
    """(reference: DefaultCrypto.Secp256K1Decrypt, DefaultCrypto.cs:320-336)"""
    if len(data) < 33 + 12 + 16:
        raise ValueError("ECIES payload too short")
    key = ecdh_shared_secret(priv, data[:33])
    return aes_gcm_decrypt(key, data[33:])


@metrics.timed("crypto_ec_recover")
def recover_hash(msg_hash: bytes, sig: bytes) -> Optional[bytes]:
    """Recover the compressed public key from a 65-byte signature."""
    lib = _native_lib()
    if lib is not None and len(msg_hash) == 32:
        import ctypes as _ct

        out = (_ct.c_ubyte * 33)()
        if lib.lt_ec_recover(msg_hash, sig, len(sig), out) == 0:
            return bytes(out)
        return None
    return _recover_hash_py(msg_hash, sig)


# batches at least this large route to the TPU recover kernel when a chip
# is present (ops/psecp.py: per-lane windowed scalar muls on the MXU);
# smaller batches, and every recover_address_batch_host, stay on the
# native threaded path
import os as _os_mod

_TPU_RECOVER_MIN = int(_os_mod.environ.get("LTPU_TPU_ECDSA_MIN", "2048"))
_tpu_recoverer: list = []  # [TpuEcdsaRecover] once built


def _tpu_recover(hashes, sigs):
    """TPU batch recovery, or None when this process owns no chip
    (crypto/provider.device_platform decides; a host-backend process never
    imports jax here). A failure on the chip propagates."""
    from .provider import device_platform

    if device_platform() != "tpu":
        return None
    if not _tpu_recoverer:
        from ..ops.psecp import TpuEcdsaRecover

        _tpu_recoverer.append(TpuEcdsaRecover())
    out = _tpu_recoverer[0].recover_batch(list(hashes), list(sigs))
    metrics.inc("crypto_tpu_ecdsa_recover_batches_total")
    return out


@functools.cache
def _batch_threads() -> int:
    """Threads a batch entry offers the library: the host's cores, 16 at
    most. Read once a process: os.cpu_count() reads a file, 36-80 us a
    call on the chip's host, where a batch of one recovery costs ~70."""
    return min(_os_mod.cpu_count() or 1, 16)


def _native_batch(entry, width, hashes, sigs, regular, nthreads):
    """One threaded library call (lt_ec_recover_batch's signature) over the
    items at `regular`, each a 32-byte hash and a 65-byte signature: for
    each, in that order, its `width` bytes of answer or None."""
    import ctypes as _ct

    m = len(regular)
    outs = _ct.create_string_buffer(width * m)
    oks = _ct.create_string_buffer(m)
    entry(
        b"".join(hashes[i] for i in regular),
        b"".join(sigs[i] for i in regular),
        m,
        nthreads or _batch_threads(),
        outs,
        oks,
    )
    raw, ok = outs.raw, oks.raw
    return [
        raw[width * pos : width * (pos + 1)] if ok[pos] == 1 else None
        for pos in range(m)
    ]


@metrics.timed("crypto_ec_recover_batch")
def recover_hash_batch(
    hashes: Sequence[bytes],
    sigs: Sequence[bytes],
    nthreads: Optional[int] = None,
) -> List[Optional[bytes]]:
    """Recover many signatures at once through the native threaded batch
    entry (lt_ec_recover_batch) — the pool-ingest path (role of the
    reference's background TransactionVerifier,
    Blockchain/Operations/TransactionVerifier.cs:23-72). Threads scale on
    multi-core hosts; on one core the batch is the scalar recovery an
    item. Entries with non-standard lengths fall back to the scalar
    path."""
    n = len(hashes)
    if n != len(sigs):
        raise ValueError("hashes/sigs length mismatch")
    lib = _native_lib()
    regular = [
        i
        for i in range(n)
        if len(hashes[i]) == 32 and len(sigs[i]) == 65
    ]
    out: List[Optional[bytes]] = [None] * n
    if lib is None or not regular:
        return [recover_hash(h, s) for h, s in zip(hashes, sigs)]
    if len(regular) >= _TPU_RECOVER_MIN:
        tpu_out = _tpu_recover(
            [hashes[i] for i in regular], [sigs[i] for i in regular]
        )
        if tpu_out is not None:
            for pos, i in enumerate(regular):
                out[i] = tpu_out[pos]
            # irregular entries keep the scalar path (same contract as the
            # native route below): identical results with or without a chip
            regular_set = set(regular)
            for i in range(n):
                if i not in regular_set:
                    out[i] = recover_hash(hashes[i], sigs[i])
            return out
    for i, pub in zip(
        regular,
        _native_batch(
            lib.lt_ec_recover_batch, 33, hashes, sigs, regular, nthreads
        ),
    ):
        out[i] = pub
    regular_set = set(regular)
    for i in range(n):
        if i not in regular_set:
            out[i] = recover_hash(hashes[i], sigs[i])
    return out


def _address_of(pub: Optional[bytes]) -> Optional[bytes]:
    return None if pub is None else address_from_public_key(pub)


def recover_address_batch(
    hashes: Sequence[bytes],
    sigs: Sequence[bytes],
    nthreads: Optional[int] = None,
) -> List[Optional[bytes]]:
    """The signers' 20-byte addresses (None where a signature is invalid)
    by any route: at _TPU_RECOVER_MIN regular items on a chip, from the
    keys of recover_hash_batch's chip route (ops/psecp.py), each
    decompressed by a Python modular square root (~140 us) for its
    keccak; otherwise recover_address_batch_host. Both give
    address_from_public_key(_recover_hash_py(h, s)) or None. The node
    resolves senders through recover_address_batch_host alone; the
    benchmark's reference (perfbench/reference_share.py) calls this one,
    so on a chip it derives a block's senders without the library's
    address entry that the node's order comes from."""
    if len(hashes) == len(sigs) and _native_lib() is not None:
        regular = sum(
            1 for h, s in zip(hashes, sigs) if len(h) == 32 and len(s) == 65
        )
        if regular >= _TPU_RECOVER_MIN:
            from .provider import device_platform

            if device_platform() == "tpu":
                return [_address_of(p) for p in recover_hash_batch(hashes, sigs)]
    return recover_address_batch_host(hashes, sigs, nthreads)


@metrics.timed("crypto_ec_recover_address_batch")
def recover_address_batch_host(
    hashes: Sequence[bytes],
    sigs: Sequence[bytes],
    nthreads: Optional[int] = None,
) -> List[Optional[bytes]]:
    """The signers' 20-byte addresses (None where a signature is invalid)
    in ONE native call, lt_ec_recover_address_batch: the library hashes
    the affine point its recovery already holds, so no key is compressed
    only to be decompressed again (a Python modular square root, ~140 us)
    for its keccak. What core/types.py resolves senders through; a batch
    of one is the scalar path. Regular items take this entry at every
    size, on a chip too: an address from the chip route's keys costs that
    square root a key. Every route gives
    address_from_public_key(_recover_hash_py(h, s)) or None, and the ones
    without the native entry derive it exactly so, as before the entry
    existed: no library (the oracle) and an item of irregular length."""
    n = len(hashes)
    if n != len(sigs):
        raise ValueError("hashes/sigs length mismatch")
    lib = _native_lib()
    regular = [
        i
        for i in range(n)
        if len(hashes[i]) == 32 and len(sigs[i]) == 65
    ]
    if lib is None or not regular:
        return [_address_of(recover_hash(h, s)) for h, s in zip(hashes, sigs)]
    out: List[Optional[bytes]] = [None] * n
    if len(regular) < n:
        regular_set = set(regular)
        for i in range(n):
            if i not in regular_set:
                out[i] = _address_of(recover_hash(hashes[i], sigs[i]))
    for i, addr in zip(
        regular,
        _native_batch(
            lib.lt_ec_recover_address_batch, 20, hashes, sigs, regular, nthreads
        ),
    ):
        out[i] = addr
    return out


def _recover_hash_py(msg_hash: bytes, sig: bytes) -> Optional[bytes]:
    if len(sig) != 65:
        return None
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:64], "big")
    v = sig[64]
    if not (1 <= r < N and 1 <= s < N) or v > 3:
        return None
    x = r + (N if v & 2 else 0)
    if x >= P:
        return None
    y2 = (pow(x, 3, P) + 7) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        return None
    if (y & 1) != (v & 1):
        y = P - y
    rp = (x, y)
    z = int.from_bytes(msg_hash, "big") % N
    rinv = _inv(r, N)
    q = _mul(_add(_mul(rp, s), _mul(G, N - z)), rinv)
    if q is None:
        return None
    return bytes([0x02 | (q[1] & 1)]) + q[0].to_bytes(32, "big")
