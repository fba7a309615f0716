"""secp256k1 ECDSA tests: sign/verify/recover roundtrip, tamper rejection.

Mirrors the reference's CryptographyTest coverage
(test/Lachain.CryptoTest/CryptographyTest.cs) for the DefaultCrypto ECDSA
surface.
"""
import ctypes
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


# -- the native entries against the oracle where the curve code has edges --

_N, _P, _G = ec.N, ec.P, ec.G
# the endomorphism lambda * (x, y) = (beta * x, y) and its lattice basis
_LAM = 0x5363AD4CC05C30E0A5261C028812645A122E22EA20816678DF02967C1B23BD72
_A1, _B1 = 0x3086D221A7D46BCDE86C90E49284EB15, -0xE4437ED6010E88286F547FA90ABFE4C3
_GLV_G1 = (_A1 * 2**384 + _N // 2) // _N
_GLV_G2 = (-_B1 * 2**384 + _N // 2) // _N


def _glv_halves(k):
    """k = k1 + k2 * lambda (mod n) as the library splits it, each half as a
    magnitude: round(k g / 2^384) times the basis."""
    c1 = (k * _GLV_G1 + (1 << 383)) >> 384
    c2 = (k * _GLV_G2 + (1 << 383)) >> 384
    k2 = (-c1 * _B1 - c2 * _A1) % _N
    k1 = (k - k2 * _LAM) % _N
    return min(k1, _N - k1), min(k2, _N - k2)


def _widest_halves():
    """Scalars whose halves come nearest 2^128, from a seeded search."""
    rng = random.Random(41)
    ks = [rng.randrange(1, _N) for _ in range(4000)]
    first = max(ks, key=lambda k: _glv_halves(k)[0])
    second = max(ks, key=lambda k: _glv_halves(k)[1])
    return first, second


_WIDE1, _WIDE2 = _widest_halves()
# the comb's last window meets an equal point: e = 14 * 2^252 + (2^256 - n)
_COMB_DOUBLE = 14 * 2**252 + 2**256 - _N


def _lib():
    lib = ec._native_lib()
    if lib is None:
        pytest.skip("native backend unavailable")
    return lib


def _n_sign(priv, h):
    out = ctypes.create_string_buffer(65)
    return out.raw if _lib().lt_ec_sign(priv, h, out) == 0 else None


def _n_pubkey(priv):
    out = ctypes.create_string_buffer(33)
    return out.raw if _lib().lt_ec_pubkey(priv, out) == 0 else None


def _n_verify(pub, h, sig):
    assert len(pub) == 33 and len(h) == 32
    return bool(_lib().lt_ec_verify(pub, h, sig, len(sig)))


def _n_recover(h, sig):
    out = ctypes.create_string_buffer(33)
    return out.raw if _lib().lt_ec_recover(h, sig, len(sig), out) == 0 else None


def _compress(pt):
    return bytes([0x02 | (pt[1] & 1)]) + pt[0].to_bytes(32, "big")


def _sig(r, s, v):
    return r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v])


def _b32(v):
    return (v % 2**256).to_bytes(32, "big")


def _on_curve_from(x):
    """The first x' >= x that is a curve point's x, with its even y."""
    while True:
        y2 = (pow(x, 3, _P) + 7) % _P
        y = pow(y2, (_P + 1) // 4, _P)
        if y * y % _P == y2:
            return x, y if y % 2 == 0 else _P - y
        x += 1


def _off_curve_from(x):
    while True:
        y2 = (pow(x, 3, _P) + 7) % _P
        y = pow(y2, (_P + 1) // 4, _P)
        if y * y % _P != y2:
            return x
        x += 1


_R7 = ec._mul(_G, 7)  # a point with x < n: the r of the aimed signatures


def _aimed(u1, u2):
    """(hash, signature) whose recovery computes u1 * R + u2 * G: r = R's
    x, s = u1 r, z = -u2 r."""
    r = _R7[0]
    return _b32(-u2 * r % _N), _sig(r, u1 * r % _N, _R7[1] & 1)


def _agree(h, sig, pub=None):
    """Recovery and verification agree with the oracle on (h, sig); the
    verification under `pub` (default: what the oracle recovers, else a
    fixed key) too."""
    want = ec._recover_hash_py(h, sig)
    assert _n_recover(h, sig) == want
    if pub is None:
        pub = want or ec.public_key_bytes(b"\x00" * 31 + b"\x05")
    assert _n_verify(pub, h, sig) == ec._verify_hash_py(pub, h, sig)
    return want


_SCALAR_PAIRS = {
    "one_zero": (1, 0),
    "one_one": (1, 1),
    "two_one": (2, 1),
    "n-1_one": (_N - 1, 1),
    "one_n-1": (1, _N - 1),
    "n-1_n-1": (_N - 1, _N - 1),
    "lambda_one": (_LAM, 1),
    "one_lambda": (1, _LAM),
    "lambda_lambda": (_LAM, _LAM),
    "n-lambda_n-lambda": (_N - _LAM, _N - _LAM),
    "2^128_2^128-1": (2**128, 2**128 - 1),
    "half_n_both": (_N // 2, _N // 2 + 1),
    "wide_halves": (_WIDE1, _WIDE2),
    "wide_halves_swapped": (_WIDE2, _WIDE1),
    "wide_halves_negated": (_N - _WIDE1, _N - _WIDE2),
}


@pytest.mark.parametrize("name", sorted(_SCALAR_PAIRS))
def test_native_scalar_edges_match_the_oracle(name):
    """Recovery's u1 * R + u2 * G at the scalars where the split and the
    wNAF have edges, and verification of the key it gives."""
    h, sig = _aimed(*_SCALAR_PAIRS[name])
    assert _agree(h, sig) is not None


def _infinite_recovery():
    # s R = z G with R = 7 G: z = 7 s
    s = 0x1234567
    return _b32(7 * s % _N), _sig(_R7[0], s, _R7[1] & 1)


def _infinite_verification():
    # u1 G + u2 Q = 0 with Q = d G: z = -r d
    d, r, s = 11, _R7[0], 0x7654321
    pub = _compress(ec._mul(_G, d))
    return _b32(-r * d % _N), _sig(r, s, 0), pub


def _x_at_least_n(odd):
    x, y = _on_curve_from(_N + 1)
    return x - _N, (2 | (y & 1)) ^ odd


_R_HIGH, _V_HIGH = _x_at_least_n(0)
_R_HIGH_ODD, _V_HIGH_ODD = _x_at_least_n(1)
_H = keccak256(b"edge")
_S = 0x3141592653589793238462643383279502884197169399375105820974944592
_R_OFF = _off_curve_from(5)

_SIG_CASES = {
    "r_zero": (_H, _sig(0, _S, 0)),
    "s_zero": (_H, _sig(_R7[0], 0, 0)),
    "r_n": (_H, _sig(_N, _S, 0)),
    "s_n": (_H, _sig(_R7[0], _N, 0)),
    "v_4": (_H, _sig(_R7[0], _S, 4)),
    "v_27": (_H, _sig(_R7[0], _S, 27)),
    "v_255": (_H, _sig(_R7[0], _S, 255)),
    "r_off_curve": (_H, _sig(_R_OFF, _S, 0)),
    "x_at_least_n": (_H, _sig(_R_HIGH, _S, _V_HIGH)),
    "x_at_least_n_odd_y": (_H, _sig(_R_HIGH_ODD, _S, _V_HIGH_ODD)),
    "x_at_least_n_flag_missing": (_H, _sig(_R_HIGH, _S, _V_HIGH & 1)),
    "x_is_p": (_H, _sig(_P - _N, _S, 2)),
    "x_past_2^256": (_H, _sig(_N - 1, _S, 3)),
    "hash_zero": (bytes(32), _sig(_R7[0], _S, 1)),
    "hash_n": (_b32(_N), _sig(_R7[0], _S, 0)),
    "hash_above_n": (_b32(_N + 5), _sig(_R7[0], _S, 0)),
    "hash_all_ones": (b"\xff" * 32, _sig(_R7[0], _S, 1)),
    "s_half_n": (_H, _sig(_R7[0], _N // 2, 0)),
    "s_half_n_plus_one": (_H, _sig(_R7[0], _N // 2 + 1, 1)),
    "recovers_infinity": _infinite_recovery(),
    "short_signature": (_H, _sig(_R7[0], _S, 0)[:64]),
}


@pytest.mark.parametrize("name", sorted(_SIG_CASES))
def test_native_signature_edges_match_the_oracle(name):
    """Malformed and boundary signatures: recovery and verification give
    the oracle's answer, a key or None, True or False."""
    _agree(*_SIG_CASES[name])


def test_native_x_at_least_n_verifies_through_r_plus_n():
    """A signature whose point has n <= x < p (v carries the flag) verifies
    under the key it recovers, on both sides, through x = r + n."""
    h, sig = _H, _sig(_R_HIGH, _S, _V_HIGH)
    pub = _agree(h, sig)
    assert pub is not None
    assert _n_verify(pub, h, sig) and ec._verify_hash_py(pub, h, sig)


def test_native_verification_at_infinity_is_false():
    h, sig, pub = _infinite_verification()
    assert _n_verify(pub, h, sig) is False
    assert ec._verify_hash_py(pub, h, sig) is False


_BAD_PUBS = {
    "prefix_00": b"\x00" + _R7[0].to_bytes(32, "big"),
    "prefix_04": b"\x04" + _R7[0].to_bytes(32, "big"),
    "prefix_05": b"\x05" + _R7[0].to_bytes(32, "big"),
    "prefix_ff": b"\xff" + _R7[0].to_bytes(32, "big"),
    "x_is_p": b"\x02" + _P.to_bytes(32, "big"),
    "x_all_ones": b"\x03" + b"\xff" * 32,
    "x_off_curve": b"\x02" + _R_OFF.to_bytes(32, "big"),
    "x_zero": b"\x02" + bytes(32),
}


@pytest.mark.parametrize("name", sorted(_BAD_PUBS))
def test_native_bad_public_key_matches_the_oracle(name):
    h, sig = _aimed(3, 5)
    pub = _BAD_PUBS[name]
    assert _n_verify(pub, h, sig) == ec._verify_hash_py(pub, h, sig) is False


_PRIVS = {
    "one": 1,
    "two": 2,
    "three": 3,
    "n-1": _N - 1,
    "n-2": _N - 2,
    "lambda": _LAM,
    "n-lambda": _N - _LAM,
    "2^128": 2**128,
    "2^255": 2**255,
    "half_n": _N // 2,
    "half_n_plus_one": _N // 2 + 1,
    "wide_halves": _WIDE1,
    "comb_last_window_doubles": _COMB_DOUBLE,
    "comb_last_window_doubles_even": _N - _COMB_DOUBLE,
}
_HASHES = {
    "zero": bytes(32),
    "above_n": _b32(_N + 5),
    "all_ones": b"\xff" * 32,
    "plain": keccak256(b"plain"),
}


@pytest.mark.parametrize("hname", sorted(_HASHES))
@pytest.mark.parametrize("pname", sorted(_PRIVS))
def test_native_sign_edges_match_the_oracle(pname, hname):
    """Signing and the public key at private keys where the comb has
    edges: the oracle's bytes; the signature verifies and recovers."""
    priv, h = _b32(_PRIVS[pname]), _HASHES[hname]
    pub = _compress(ec._mul(_G, _PRIVS[pname]))
    assert _n_pubkey(priv) == pub
    sig = _n_sign(priv, h)
    assert sig == ec._sign_hash_py(priv, h)
    assert _n_verify(pub, h, sig) and _n_recover(h, sig) == pub


@pytest.mark.parametrize("priv", [0, _N, _N + 1, 2**256 - 1])
def test_native_sign_refuses_a_key_out_of_range(priv):
    assert _n_pubkey(_b32(priv)) is None
    assert _n_sign(_b32(priv), _H) is None


@pytest.mark.parametrize("seed", range(12))
def test_native_random_keys_match_the_oracle(seed):
    """Seeded random keys and hashes: sign, key, verify and recover are the
    oracle's, and so are both sides' answers for a tampered signature."""
    rng = random.Random(1000 + seed)
    for _ in range(16):
        d = rng.randrange(1, _N)
        priv, h = _b32(d), rng.randbytes(32)
        pub = _compress(ec._mul(_G, d))
        assert _n_pubkey(priv) == pub
        sig = _n_sign(priv, h)
        assert sig == ec._sign_hash_py(priv, h)
        assert _n_verify(pub, h, sig) and _n_recover(h, sig) == pub
        bad = bytearray(sig)
        bad[rng.randrange(65)] ^= 1 << rng.randrange(8)
        _agree(h, bytes(bad), pub)


def _batch_items(n):
    """n (pub, hash, sig) with every third item from the second on made
    invalid a different way."""
    rng = random.Random(n)
    items = []
    for i in range(n):
        priv = _b32(rng.randrange(1, _N))
        h = rng.randbytes(32)
        sig = _n_sign(priv, h)
        if i % 3 == 1:
            sig = [
                _sig(0, 1, 0),
                sig[:64] + b"\x05",
                sig[:10] + bytes([sig[10] ^ 0x40]) + sig[11:],
                _sig(_R_OFF, 7, 1),
            ][(i // 3) % 4]
        items.append((_n_pubkey(priv), h, sig))
    return items


def _batch_lib():
    """Its own handle on the library, with the batch entries' types."""
    lib = ctypes.CDLL(_lib()._name)
    buf, size = ctypes.c_char_p, ctypes.c_size_t
    for name in ("lt_ec_recover_batch", "lt_ec_recover_address_batch"):
        fn = getattr(lib, name)
        fn.argtypes = [buf, buf, size, ctypes.c_int, buf, buf]
        fn.restype = ctypes.c_int
    lib.lt_ec_verify_batch.argtypes = [buf, buf, buf, size, ctypes.c_int, buf]
    lib.lt_ec_verify_batch.restype = ctypes.c_int
    return lib


@pytest.mark.parametrize("threads", [1, 4])
@pytest.mark.parametrize("n", [1, 2, 64])
@pytest.mark.parametrize("entry", ["recover", "recover_address", "verify"])
def test_native_batch_entries_match_the_scalar_entries(entry, n, threads):
    lib = _batch_lib()
    items = _batch_items(n)
    pubs = b"".join(p for p, _, _ in items)
    hashes = b"".join(h for _, h, _ in items)
    sigs = b"".join(s for _, _, s in items)
    oks = ctypes.create_string_buffer(n)
    if entry == "verify":
        lib.lt_ec_verify_batch(pubs, hashes, sigs, n, threads, oks)
        got = [bool(b) for b in oks.raw]
        want = [_n_verify(p, h, s) for p, h, s in items]
    else:
        width = 33 if entry == "recover" else 20
        outs = ctypes.create_string_buffer(width * n)
        getattr(lib, "lt_ec_" + entry + "_batch")(hashes, sigs, n, threads, outs, oks)
        got = [
            outs.raw[width * i : width * (i + 1)] if oks.raw[i] else None
            for i in range(n)
        ]
        keys = [_n_recover(h, s) for _, h, s in items]
        want = keys if entry == "recover" else [
            None if k is None else ec.address_from_public_key(k) for k in keys
        ]
    assert got == want
    assert (None in want or False in want) == (n > 1)


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


def test_recover_address_batch_host_never_takes_the_chip_route(monkeypatch):
    """The node's address route: on a chip, at any size, addresses come
    from the native address entry. recover_address_batch_host never takes
    recover_hash_batch's device route, which returns keys, and gives the
    oracle's addresses, an irregular item still None; recover_address_batch
    at the same threshold still takes the chip route."""
    from lachain_tpu.crypto import ecdsa, provider

    privs = [ecdsa.generate_private_key() for _ in range(5)]
    hashes = [bytes([i]) * 32 for i in range(5)]
    sigs = [ecdsa.sign_hash(p, h) for p, h in zip(privs, hashes)]
    sigs[3] = sigs[3][:64]
    want = []
    for h, s in zip(hashes, sigs):
        pub = ecdsa._recover_hash_py(h, s)
        want.append(None if pub is None else ecdsa.address_from_public_key(pub))
    assert want[3] is None and None not in want[:3] and want[4] is not None
    handed = []

    def device(hs, ss):
        handed.append(len(hs))
        return [ecdsa.recover_hash(h, s) for h, s in zip(hs, ss)]

    monkeypatch.setattr(ecdsa, "_TPU_RECOVER_MIN", 4)
    monkeypatch.setattr(provider, "device_platform", lambda: "tpu")
    monkeypatch.setattr(ecdsa, "_tpu_recover", device)
    assert ecdsa.recover_address_batch_host(hashes, sigs) == want
    assert ecdsa.recover_address_batch_host(hashes * 3, sigs * 3) == want * 3
    assert ecdsa.recover_address_batch_host(hashes[:3], sigs[:3]) == want[:3]
    assert handed == []
    assert ecdsa.recover_address_batch(hashes, sigs) == want
    assert handed == [4]  # the four regular items, on the chip route


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
