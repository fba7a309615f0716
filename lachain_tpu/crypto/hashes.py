"""Hash primitives: Keccak-256, SHA-256, Ripemd160, Merkle tree, XOF.

Parity with the reference's hashing layer
(/root/reference/src/Lachain.Crypto/HashUtils.cs:1-86 and
Misc/MerkleTree.cs:183-198). Keccak-256 (the legacy pre-NIST padding used by
Ethereum and the reference's `KeccakDigest(256)`) is implemented natively here
since hashlib only ships NIST SHA-3.
"""
from __future__ import annotations

import hashlib
from typing import List, NamedTuple, Optional, Sequence

_KECCAK_ROUNDS = 24
_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]
_ROT = [
    [0, 36, 3, 41, 18],
    [1, 44, 10, 45, 2],
    [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56],
    [27, 20, 39, 8, 14],
]
_MASK = (1 << 64) - 1


def _rol(v: int, s: int) -> int:
    return ((v << s) | (v >> (64 - s))) & _MASK


def _keccak_f(a: List[List[int]]) -> None:
    for rnd in range(_KECCAK_ROUNDS):
        # theta
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        for x in range(5):
            for y in range(5):
                a[x][y] ^= d[x]
        # rho + pi
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rol(a[x][y], _ROT[x][y])
        # chi
        for x in range(5):
            for y in range(5):
                a[x][y] = b[x][y] ^ ((~b[(x + 1) % 5][y]) & b[(x + 2) % 5][y])
        # iota
        a[0][0] ^= _RC[rnd]


_native_cache = [False, None]


def _native_lib():
    """The C++ backend's lt_keccak256 (cross-checked against the pure-Python
    implementation below in tests/test_hashes.py). Keccak dominates tx/block
    hashing, so the dispatch matters for pool ingest and block execution."""
    if not _native_cache[0]:
        _native_cache[0] = True
        import os as _os

        if _os.environ.get("LACHAIN_TPU_HASHES") != "python":
            try:
                from .native_backend import load_lib

                _native_cache[1] = load_lib()
            except Exception:
                # a library that fails to build is an error unless the
                # Python oracle was asked for (crypto/provider.py rule)
                if _os.environ.get("LACHAIN_TPU_BACKEND") != "python":
                    raise
    return _native_cache[1]


def keccak256(data: bytes) -> bytes:
    """Keccak-256 with legacy 0x01 padding (Ethereum-style), not SHA3-256."""
    lib = _native_lib()
    if lib is not None:
        import ctypes as _ct

        out = (_ct.c_ubyte * 32)()
        lib.lt_keccak256(data, len(data), out)
        return bytes(out)
    return _keccak256_py(data)


_batch_cache = [False, None]


def _batch_fn():
    """lt_keccak256_batch from the native backend, or None. Separate probe
    from _native_lib so a stale libbls381.so (built before the batch entry
    point existed) degrades to per-item dispatch instead of failing."""
    if not _batch_cache[0]:
        _batch_cache[0] = True
        lib = _native_lib()
        if lib is not None:
            import ctypes as _ct

            try:
                fn = lib.lt_keccak256_batch
            except AttributeError:
                fn = None
            else:
                fn.argtypes = [
                    _ct.c_char_p,
                    _ct.POINTER(_ct.c_uint64),
                    _ct.c_size_t,
                    _ct.c_int,
                    _ct.POINTER(_ct.c_ubyte),
                ]
                fn.restype = _ct.c_int
            _batch_cache[1] = fn
    return _batch_cache[1]


def keccak256_batch(items: Sequence[bytes], nthreads: int = 0) -> List[bytes]:
    """Keccak-256 over a whole batch in ONE native call (threaded in C++,
    GIL released) — the trie commit path hashes ~100k node encodings per
    10k-tx block, and per-item ctypes dispatch is most of that wall.
    Falls back to per-item keccak256 when the native entry is unavailable."""
    n = len(items)
    if n == 0:
        return []
    fn = _batch_fn()
    if fn is None:
        return [keccak256(d) for d in items]
    import ctypes as _ct
    import os as _os

    if nthreads <= 0:
        nthreads = min(_os.cpu_count() or 1, 16)
    offsets = (_ct.c_uint64 * (n + 1))()
    total = 0
    for i, d in enumerate(items):
        offsets[i] = total
        total += len(d)
    offsets[n] = total
    data = b"".join(items)
    out = (_ct.c_ubyte * (n * 32))()
    rc = fn(data, offsets, n, nthreads, out)
    if rc != 0:
        return [keccak256(d) for d in items]
    raw = bytes(out)
    return [raw[i * 32 : (i + 1) * 32] for i in range(n)]


def _keccak256_py(data: bytes) -> bytes:
    rate = 136
    state = [[0] * 5 for _ in range(5)]
    padded = bytearray(data)
    padded.append(0x01)
    while len(padded) % rate:
        padded.append(0x00)
    padded[-1] |= 0x80
    for off in range(0, len(padded), rate):
        block = padded[off : off + rate]
        for i in range(rate // 8):
            lane = int.from_bytes(block[i * 8 : i * 8 + 8], "little")
            state[i % 5][i // 5] ^= lane
        _keccak_f(state)
    out = bytearray()
    for i in range(4):  # 32 bytes = 4 lanes
        out += state[i % 5][i // 5].to_bytes(8, "little")
    return bytes(out)


def sha256(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def ripemd160(data: bytes) -> bytes:
    h = hashlib.new("ripemd160")
    h.update(data)
    return h.digest()


def xof(domain: bytes, data: bytes, nbytes: int) -> bytes:
    """SHAKE-256 XOF with domain separation — keystream generator for the TPKE
    XOR pad (role of the reference's SHA3-seeded DigestRandomGenerator,
    /root/reference/src/Lachain.Crypto/TPKE/Utils.cs:13-19; our chain defines
    a cleaner XOF rather than reproducing BouncyCastle bit-exactly)."""
    h = hashlib.shake_256()
    h.update(len(domain).to_bytes(1, "big") + domain + data)
    return h.digest(nbytes)


def merkle_root(leaves: Sequence[bytes]) -> Optional[bytes]:
    """Binary Merkle root over 32-byte leaf hashes.

    Shape parity with MerkleTree.ComputeRoot
    (/root/reference/src/Lachain.Crypto/Misc/MerkleTree.cs:183-198): pairwise
    keccak256(left || right), odd node promoted unchanged.
    """
    if not leaves:
        return None
    level = list(leaves)
    while len(level) > 1:
        nxt = []
        for i in range(0, len(level) - 1, 2):
            nxt.append(keccak256(level[i] + level[i + 1]))
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    return level[0]


def merkle_proof(leaves: Sequence[bytes], index: int) -> List[bytes]:
    """Sibling path for leaves[index]; verify with merkle_verify."""
    proof: List[bytes] = []
    level = list(leaves)
    idx = index
    while len(level) > 1:
        nxt = []
        for i in range(0, len(level) - 1, 2):
            nxt.append(keccak256(level[i] + level[i + 1]))
        if len(level) % 2:
            nxt.append(level[-1])
        sib = idx ^ 1
        if sib < len(level):
            proof.append(level[sib])
        else:
            proof.append(b"")  # odd promotion: no sibling at this level
        idx //= 2
        level = nxt
    return proof


class MerkleTree(NamedTuple):
    root: Optional[bytes]  # what merkle_root(leaves) gives
    branches: List[List[bytes]]  # branches[i] == merkle_proof(leaves, i)
    hashes: int  # keccak256 calls made: one an inner node, N - 1 in all


def merkle_tree(leaves: Sequence[bytes]) -> MerkleTree:
    """Root and every leaf's sibling path from ONE bottom-up pass: N - 1
    hashes where merkle_proof in a loop over the leaves makes N * (N - 1).
    RBC's VAL fan-out needs all N branches of a proposal's tree;
    merkle_proof stays the tool for one branch and the reference this is
    tested against (same shape, b"" where an odd node was promoted)."""
    n = len(leaves)
    if not n:
        return MerkleTree(None, [], 0)
    branches: List[List[bytes]] = [[] for _ in range(n)]
    idxs = list(range(n))  # each leaf's ancestor's position in `level`
    level = list(leaves)
    hashed = 0
    while len(level) > 1:
        for branch, idx in zip(branches, idxs):
            sib = idx ^ 1
            branch.append(level[sib] if sib < len(level) else b"")
        nxt = [
            keccak256(level[i] + level[i + 1])
            for i in range(0, len(level) - 1, 2)
        ]
        hashed += len(nxt)
        if len(level) % 2:
            nxt.append(level[-1])
        idxs = [idx // 2 for idx in idxs]
        level = nxt
    return MerkleTree(level[0], branches, hashed)


def merkle_verify(
    leaf: bytes, index: int, proof: Sequence[bytes], root: bytes
) -> bool:
    node = leaf
    idx = index
    for sib in proof:
        if sib == b"":
            pass  # promoted unchanged
        elif idx % 2 == 0:
            node = keccak256(node + sib)
        else:
            node = keccak256(sib + node)
        idx //= 2
    return node == root
