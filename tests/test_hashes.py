"""Hash primitive tests (known-answer vectors + Merkle tree).

Merkle shape mirrors the reference's MerkleTree usage in ReliableBroadcast
(/root/reference/src/Lachain.Consensus/ReliableBroadcast/ReliableBroadcast.cs:296-309).
"""
from lachain_tpu.crypto import hashes
import pytest


def test_keccak256_vectors():
    # Well-known Keccak-256 (pre-NIST padding) vectors.
    assert (
        hashes.keccak256(b"").hex()
        == "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
    )
    assert (
        hashes.keccak256(b"abc").hex()
        == "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45"
    )
    # multi-block input (> 136-byte rate)
    long = b"a" * 300
    assert len(hashes.keccak256(long)) == 32
    assert hashes.keccak256(long) != hashes.keccak256(b"a" * 299)


def test_xof_domain_separation():
    a = hashes.xof(b"d1", b"msg", 64)
    b = hashes.xof(b"d2", b"msg", 64)
    assert a != b
    assert len(a) == 64
    assert hashes.xof(b"d1", b"msg", 64) == a


def test_merkle_root_and_proof():
    leaves = [hashes.keccak256(bytes([i])) for i in range(7)]
    root = hashes.merkle_root(leaves)
    assert root is not None
    for i, leaf in enumerate(leaves):
        proof = hashes.merkle_proof(leaves, i)
        assert hashes.merkle_verify(leaf, i, proof, root)
        # wrong index / wrong leaf fail
        assert not hashes.merkle_verify(leaf, (i + 1) % 7, proof, root)
        assert not hashes.merkle_verify(hashes.keccak256(b"x"), i, proof, root)
    assert hashes.merkle_root([]) is None
    assert hashes.merkle_root([leaves[0]]) == leaves[0]


def test_merkle_sizes():
    for n in (1, 2, 3, 4, 5, 8, 16, 31):
        leaves = [hashes.keccak256(bytes([i, n])) for i in range(n)]
        root = hashes.merkle_root(leaves)
        for i in range(n):
            proof = hashes.merkle_proof(leaves, i)
            assert hashes.merkle_verify(leaves[i], i, proof, root), (n, i)


def test_native_keccak_matches_python():
    import random

    from lachain_tpu.crypto.hashes import _keccak256_py, _native_lib, keccak256

    if _native_lib() is None:
        import pytest

        pytest.skip("native backend unavailable")
    rng = random.Random(3)
    for size in (0, 1, 31, 32, 135, 136, 137, 1000, 5000):
        data = rng.randbytes(size)
        assert keccak256(data) == _keccak256_py(data)

def test_native_keccak_batch_matches_python():
    """lt_keccak256_batch cross-check against the pure-Python sponge on
    randomized lengths, the sponge-rate boundary (135/136/137) and the
    empty input — single-threaded AND threaded must agree item-for-item."""
    import random

    from lachain_tpu.crypto.hashes import (
        _batch_fn,
        _keccak256_py,
        keccak256_batch,
    )

    if _batch_fn() is None:
        pytest.skip("native batch keccak unavailable")
    rng = random.Random(7)
    items = [b"", rng.randbytes(135), rng.randbytes(136), rng.randbytes(137)]
    items += [rng.randbytes(rng.randrange(0, 600)) for _ in range(300)]
    rng.shuffle(items)
    expect = [_keccak256_py(d) for d in items]
    assert keccak256_batch(items, 1) == expect
    assert keccak256_batch(items, 4) == expect
    assert keccak256_batch([], 4) == []
    # a single item still round-trips through the batch entry point
    assert keccak256_batch([b"abc"], 1) == [_keccak256_py(b"abc")]


def test_keccak_batch_python_fallback():
    """With the native path disabled the batch API must fall back to the
    per-item implementation (stale .so / LACHAIN_TPU_HASHES=python)."""
    from lachain_tpu.crypto import hashes

    saved = hashes._batch_cache[:]
    try:
        hashes._batch_cache[0] = True
        hashes._batch_cache[1] = None
        data = [b"", b"abc", b"x" * 137]
        assert hashes.keccak256_batch(data, 4) == [
            hashes.keccak256(d) for d in data
        ]
    finally:
        hashes._batch_cache[0] = saved[0]
        hashes._batch_cache[1] = saved[1]


# slice marker: crypto/accelerator kernels ("make test-kernel")
pytestmark = pytest.mark.kernel


def _leaves(n):
    return [hashes.keccak256(bytes([i % 256, i // 256, n % 256])) for i in range(n)]


@pytest.mark.parametrize("n", [1, 2, 3, 5, 7, 8, 16, 22, 64, 65, 255])
def test_merkle_tree_is_merkle_proof_for_every_leaf(n):
    """One bottom-up pass gives what merkle_root and N merkle_proof calls
    give: same root, same branches (b"" at an odd promotion), all valid."""
    leaves = _leaves(n)
    tree = hashes.merkle_tree(leaves)
    assert tree.root == hashes.merkle_root(leaves)
    assert len(tree.branches) == n
    for i in range(n):
        assert tree.branches[i] == hashes.merkle_proof(leaves, i), (n, i)
        assert hashes.merkle_verify(leaves[i], i, tree.branches[i], tree.root)


def test_merkle_tree_hashes_once_a_node(monkeypatch):
    """A 64-leaf tree with its 64 branches costs O(N) keccaks, not N^2."""
    leaves = _leaves(64)
    want = hashes.merkle_root(leaves)
    calls = []
    real = hashes.keccak256
    monkeypatch.setattr(hashes, "keccak256", lambda d: calls.append(1) or real(d))
    tree = hashes.merkle_tree(leaves)
    assert tree.root == want
    assert len(calls) == tree.hashes == 63  # the contract: at most 2N


def test_merkle_tree_of_nothing():
    tree = hashes.merkle_tree([])
    assert tree.root is None and hashes.merkle_root([]) is None
    assert tree.branches == [] and tree.hashes == 0
