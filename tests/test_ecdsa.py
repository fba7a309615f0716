"""secp256k1 ECDSA tests: sign/verify/recover roundtrip, tamper rejection.

Mirrors the reference's CryptographyTest coverage
(test/Lachain.CryptoTest/CryptographyTest.cs) for the DefaultCrypto ECDSA
surface.
"""
import random

from lachain_tpu.crypto import ecdsa as ec
from lachain_tpu.crypto.hashes import keccak256
import pytest


class Rng:
    def __init__(self, seed):
        self._r = random.Random(seed)

    def randbelow(self, n):
        return self._r.randrange(n)


def test_sign_verify_recover_roundtrip():
    rng = Rng(1)
    for i in range(4):
        priv = ec.generate_private_key(rng)
        pub = ec.public_key_bytes(priv)
        h = keccak256(b"message %d" % i)
        sig = ec.sign_hash(priv, h)
        assert len(sig) == 65
        assert ec.verify_hash(pub, h, sig)
        assert ec.recover_hash(h, sig) == pub


def test_signature_is_deterministic():
    priv = ec.generate_private_key(Rng(2))
    h = keccak256(b"rfc6979")
    assert ec.sign_hash(priv, h) == ec.sign_hash(priv, h)


def test_tampered_signature_rejected():
    rng = Rng(3)
    priv = ec.generate_private_key(rng)
    pub = ec.public_key_bytes(priv)
    h = keccak256(b"tamper")
    sig = bytearray(ec.sign_hash(priv, h))
    sig[10] ^= 1
    assert not ec.verify_hash(pub, h, bytes(sig))
    assert ec.recover_hash(h, bytes(sig)) != pub
    # wrong message
    good = ec.sign_hash(priv, h)
    assert not ec.verify_hash(pub, keccak256(b"other"), good)


def test_low_s_enforced():
    rng = Rng(4)
    priv = ec.generate_private_key(rng)
    for i in range(8):
        sig = ec.sign_hash(priv, keccak256(bytes([i])))
        s = int.from_bytes(sig[32:64], "big")
        assert s <= ec.N // 2


def test_address_derivation():
    priv = ec.generate_private_key(Rng(5))
    pub = ec.public_key_bytes(priv)
    addr = ec.address_from_public_key(pub)
    assert len(addr) == 20
    # deterministic
    assert ec.address_from_public_key(pub) == addr


def test_malformed_inputs():
    h = keccak256(b"x")
    assert not ec.verify_hash(b"\x02" + b"\xff" * 32, h, b"\x00" * 65)
    assert ec.recover_hash(h, b"\x00" * 65) is None
    assert ec.recover_hash(h, b"short") is None


def test_malformed_pubkey_prefix_agrees_across_backends():
    """A garbage pubkey (bad prefix byte, wrong length) must be a clean
    False on BOTH backends — never an exception. A python-node trap where
    a native node returns 0 would fork state on contract crypto_verify
    (ADVICE round 2, high)."""
    priv = ec.generate_private_key(Rng(11))
    h = keccak256(b"payload")
    sig = ec.sign_hash(priv, h)
    for bad_pub in (
        b"\x04" + b"\x11" * 32,   # uncompressed prefix, 33 bytes
        b"\x00" + b"\x11" * 32,   # zero prefix
        b"\xff" + b"\x11" * 32,   # junk prefix
        b"\x02" + b"\x11" * 31,   # short
        b"\x02" + b"\x11" * 40,   # long
        b"",                       # empty
    ):
        assert ec._verify_hash_py(bad_pub, h, sig) is False
        assert ec.verify_hash(bad_pub, h, sig) is False


def test_native_backend_matches_python_oracle():
    """The C++ secp256k1 backend must be byte-identical to the pure-Python
    oracle on sign/verify/recover (round-2 native TransactionVerifier
    prerequisite)."""
    import random

    from lachain_tpu.crypto.ecdsa import (
        _native_lib,
        _recover_hash_py,
        _sign_hash_py,
        _verify_hash_py,
        generate_private_key,
        public_key_bytes,
        recover_hash,
        sign_hash,
        verify_hash,
    )

    if _native_lib() is None:
        import pytest

        pytest.skip("native backend unavailable")
    rng = random.Random(7)

    class R:
        def randbelow(self, n):
            return rng.randrange(n)

    for _ in range(20):
        priv = generate_private_key(R())
        h = rng.randbytes(32)
        sig = sign_hash(priv, h)
        assert sig == _sign_hash_py(priv, h)
        pub = public_key_bytes(priv)
        assert verify_hash(pub, h, sig)
        assert _verify_hash_py(pub, h, sig)
        assert recover_hash(h, sig) == pub == _recover_hash_py(h, sig)
        bad = bytearray(sig)
        bad[3] ^= 1
        assert not verify_hash(pub, h, bytes(bad))


def test_recover_hash_batch_matches_scalar():
    """Threaded batch entry (lt_ec_recover_batch) vs per-call recovery,
    including an invalid signature and a malformed-length one."""
    import random

    from lachain_tpu.crypto import ecdsa

    rng = random.Random(11)
    privs = [ecdsa.generate_private_key() for _ in range(6)]
    hashes = [bytes([rng.randrange(256) for _ in range(32)]) for _ in privs]
    sigs = [ecdsa.sign_hash(p, h) for p, h in zip(privs, hashes)]
    bad = bytearray(sigs[2])
    bad[5] ^= 0xFF
    sigs[2] = bytes(bad)
    sigs[4] = sigs[4][:40]  # malformed length -> scalar fallback lane
    got = ecdsa.recover_hash_batch(hashes, sigs)
    want = [ecdsa.recover_hash(h, s) for h, s in zip(hashes, sigs)]
    assert got == want
    assert got[0] == ecdsa.public_key_bytes(privs[0])
    assert got[4] is None


def test_recover_address_batch_matches_the_oracle():
    """The address entry (lt_ec_recover_address_batch) beside the key
    entry: every item is the oracle's key's address, threaded and not,
    with an invalid signature, one of malformed length and an empty
    batch."""
    import random

    from lachain_tpu.crypto import ecdsa

    rng = random.Random(12)
    privs = [ecdsa.generate_private_key() for _ in range(40)]
    hashes = [rng.randbytes(32) for _ in privs]
    sigs = [ecdsa.sign_hash(p, h) for p, h in zip(privs, hashes)]
    bad = bytearray(sigs[2])
    bad[5] ^= 0xFF
    sigs[2] = bytes(bad)
    sigs[4] = sigs[4][:40]
    sigs[7] = bytes(32) + sigs[7][32:]  # r = 0
    sigs[9] = sigs[9][:64] + b"\x04"  # v out of range
    want = []
    for h, s in zip(hashes, sigs):
        pub = ecdsa._recover_hash_py(h, s)
        want.append(None if pub is None else ecdsa.address_from_public_key(pub))
    assert [w is None for w in want[:10]].count(True) >= 3
    assert want[0] == ecdsa.address_from_public_key(
        ecdsa.public_key_bytes(privs[0])
    )
    for nthreads in (None, 1, 3):
        assert ecdsa.recover_address_batch(hashes, sigs, nthreads) == want
    # the key entry's answers, hashed, are the same senders
    pubs = ecdsa.recover_hash_batch(hashes, sigs)
    assert [
        None if p is None else ecdsa.address_from_public_key(p) for p in pubs
    ] == want
    assert ecdsa.recover_address_batch([], []) == []
    with pytest.raises(ValueError):
        ecdsa.recover_address_batch(hashes, sigs[:-1])


def test_recover_address_batch_on_the_chip_route(monkeypatch):
    """At _TPU_RECOVER_MIN regular items on a chip the recovery is
    recover_hash_batch's device route, which returns keys: the addresses
    are derived from them, and irregular items still take the scalar
    path."""
    from lachain_tpu.crypto import ecdsa, provider

    privs = [ecdsa.generate_private_key() for _ in range(5)]
    hashes = [bytes([i]) * 32 for i in range(5)]
    sigs = [ecdsa.sign_hash(p, h) for p, h in zip(privs, hashes)]
    sigs[3] = sigs[3][:64]
    want = ecdsa.recover_address_batch(hashes, sigs)
    assert want[3] is None and None not in want[:3]
    handed = []

    def device(hs, ss):
        handed.append(len(hs))
        return [ecdsa.recover_hash(h, s) for h, s in zip(hs, ss)]

    monkeypatch.setattr(ecdsa, "_TPU_RECOVER_MIN", 4)
    monkeypatch.setattr(provider, "device_platform", lambda: "tpu")
    monkeypatch.setattr(ecdsa, "_tpu_recover", device)
    assert ecdsa.recover_address_batch(hashes, sigs) == want
    assert handed == [4]
    assert ecdsa.recover_address_batch(hashes[:3], sigs[:3]) == want[:3]
    assert handed == [4]  # under the threshold: the native entry


def test_warm_sender_caches():
    from lachain_tpu.core.types import (
        Transaction,
        sign_transaction,
        warm_sender_caches,
    )
    from lachain_tpu.crypto import ecdsa

    chain_id = 77
    privs = [ecdsa.generate_private_key() for _ in range(4)]
    stxs = [
        sign_transaction(
            Transaction(to=b"\x01" * 20, value=5, nonce=0, gas_price=1,
                        gas_limit=21000),
            p,
            chain_id,
        )
        for p in privs
    ]
    warm_sender_caches(stxs, chain_id)
    for p, stx in zip(privs, stxs):
        cached = stx.__dict__.get("_sender_cache")
        assert cached is not None and cached[0] == chain_id
        want = ecdsa.address_from_public_key(ecdsa.public_key_bytes(p))
        assert stx.sender(chain_id) == want


def test_aes_gcm_fallback_nist_vectors():
    """The pure-Python GCM (crypto/_aes_fallback.py) that backs
    aes_gcm_encrypt when `cryptography` is absent must match NIST
    SP 800-38D reference vectors bit for bit — otherwise wallets written
    in one environment can't be read in the other."""
    from lachain_tpu.crypto import _aes_fallback as f

    assert (
        f.encrypt(bytes(16), bytes(12), b"").hex()
        == "58e2fccefa7e3061367f1d57a4e7455a"
    )
    assert f.encrypt(bytes(16), bytes(12), bytes(16)).hex() == (
        "0388dace60b6a392f328c2b971b2fe78"
        "ab6e47d42cec13bdf53a67b21257bddf"
    )
    key = bytes.fromhex("feffe9928665731c6d6a8f9467308308")
    nonce = bytes.fromhex("cafebabefacedbaddecaf888")
    pt = bytes.fromhex(
        "d9313225f88406e5a55909c5aff5269a86a7a9531534f7da2e4c303d8a318a72"
        "1c3c0c95956809532fcf0e2449a6b525b16aedf5aa0de657ba637b391aafd255"
    )
    aad = bytes.fromhex("feedfacedeadbeeffeedfacedeadbeefabaddad2")
    out = f.encrypt(key, nonce, pt)
    assert out[-16:].hex() == "4d5c2af327cd64a62cf35abd2ba6fab4"
    assert (
        f.encrypt(key, nonce, pt[:-4], aad)[-16:].hex()
        == "5bc94fbc3221a5db94fae95ae7121a47"
    )
    assert f.encrypt(bytes(24), bytes(12), bytes(16)).hex() == (
        "98e7247c07f0fe411c267e4384b0f600"
        "2ff58d80033927ab8ef4d4587514f0fb"
    )
    assert f.encrypt(bytes(32), bytes(12), bytes(16)).hex() == (
        "cea7403d4d606b6e074ec5d3baf39d18"
        "d0d1c8a799996bf0265b98b5d48ab919"
    )


def test_aes_gcm_fallback_roundtrip_and_tamper():
    import random as _random

    from lachain_tpu.crypto import _aes_fallback as f

    r = _random.Random(5)
    key = bytes(r.getrandbits(8) for _ in range(32))
    nonce = bytes(r.getrandbits(8) for _ in range(12))
    msg = bytes(r.getrandbits(8) for _ in range(999))
    ct = f.encrypt(key, nonce, msg, b"aad")
    assert f.decrypt(key, nonce, ct, b"aad") == msg
    import pytest as _pytest

    with _pytest.raises(ValueError):
        f.decrypt(key, nonce, ct[:-1] + bytes([ct[-1] ^ 1]), b"aad")
    with _pytest.raises(ValueError):
        f.decrypt(key, nonce, ct, b"wrong-aad")


def test_wallet_roundtrip_without_cryptography_package():
    """aes_gcm_encrypt/decrypt (and thus PrivateWallet save/load and the
    keygen->run CLI path) must work in containers without `cryptography`."""
    from lachain_tpu.crypto import ecdsa

    key = bytes(range(32))
    blob = ecdsa.aes_gcm_encrypt(key, b"wallet-payload" * 20)
    assert ecdsa.aes_gcm_decrypt(key, blob) == b"wallet-payload" * 20

# slice marker: crypto/accelerator kernels ("make test-kernel")
pytestmark = pytest.mark.kernel
