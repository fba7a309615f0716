"""Merkleization differentials.

The native walk of `apply_many` must be BIT-IDENTICAL to the Python
walker, which hashes each node as it is stored: same roots, same node
sets, same pending-buffer contents. These tests lock that with a 200-seed
randomized differential over mixed put/delete batches (leaf splits,
single-leaf collapses, collapses at the root) plus targeted edge cases the
fuzz can miss.
"""
import random

import pytest

import lachain_tpu.storage.trie as trie_mod
from lachain_tpu.crypto.hashes import keccak256
from lachain_tpu.storage.kv import MemoryKV
from lachain_tpu.storage.trie import EMPTY_ROOT, Trie

pytestmark = [pytest.mark.trie, pytest.mark.storage]


def _serial_oracle_apply(t: Trie, root: bytes, writes) -> bytes:
    """The Python walker: each node hashed as it is stored — the ground
    truth the native walk must match."""
    assert t._walks is None
    return t.apply_many(root, writes)


def _native() -> Trie:
    assert trie_mod.native_walks() is not None
    return Trie(MemoryKV(), native=True)


def _native_apply(t: Trie, root: bytes, writes) -> bytes:
    """The native walk: one library call for the batch."""
    assert t._walks is not None
    return t.apply_many(root, writes)


def _random_batch(rng, pool, n_ops, delete_frac):
    writes = {}
    for _ in range(n_ops):
        k = rng.choice(pool)
        writes[k] = (
            None if rng.random() < delete_frac else rng.randbytes(rng.randrange(1, 40))
        )
    return writes


@pytest.mark.parametrize("seed_base", [0, 50, 100, 150])
def test_differential_200_seeds(seed_base):
    """50 seeds per case x 4 cases = 200 randomized workloads: the Python
    walker vs the native walk: roots and pending buffers must be
    identical."""
    for seed in range(seed_base, seed_base + 50):
        rng = random.Random(seed)
        # small key pool => deletes hit existing keys, repeated puts split
        # and re-split leaves, collapses happen across batches
        pool = [rng.randbytes(rng.randrange(1, 24)) for _ in range(60)]
        t_oracle = Trie(MemoryKV())
        t_native = _native()
        root_o = root_n = EMPTY_ROOT
        for step in range(3):
            writes = _random_batch(
                rng, pool, rng.randrange(8, 80), rng.choice((0.2, 0.5, 0.8))
            )
            root_o = _serial_oracle_apply(t_oracle, root_o, dict(writes))
            root_n = _native_apply(t_native, root_n, dict(writes))
            assert root_o == root_n, (seed, step)
            assert dict(t_oracle._pending) == dict(t_native._pending), (
                seed,
                step,
            )
        # materialized state agrees too (leaf set, not just hashes)
        if root_o != EMPTY_ROOT:
            assert list(t_oracle.iter_items(root_o)) == list(
                t_native.iter_items(root_n)
            ), seed


def _key_with_first_nibble(nib: int, tag: int) -> bytes:
    """A raw key whose keccak256 hash starts with nibble `nib` — places the
    leaf in a chosen top-level subtrie."""
    i = 0
    while True:
        k = b"%d:%d:%d" % (nib, tag, i)
        if keccak256(k)[0] >> 4 == nib:
            return k
        i += 1


def test_single_leaf_collapse_across_subtrie_boundary():
    """Delete down to ONE live leaf: the root branch must collapse to that
    leaf. The native walk decides the collapse over children stored in the
    same walk, whose hashes are patched in only afterwards — the seam where
    it could diverge from the Python oracle."""
    keys = [_key_with_first_nibble(n, 0) for n in range(16)]
    for survivor in (0, 7, 15):
        t_o, t_n = Trie(MemoryKV()), _native()
        base_writes = {k: b"v%d" % i for i, k in enumerate(keys)}
        root_o = _serial_oracle_apply(t_o, EMPTY_ROOT, dict(base_writes))
        root_n = _native_apply(t_n, EMPTY_ROOT, dict(base_writes))
        assert root_o == root_n
        # one batch deletes every subtrie but one — 15 of them become
        # EMPTY_ROOT, and the walk must collapse the branch to a leaf
        deletes = {k: None for i, k in enumerate(keys) if i != survivor}
        root_o = _serial_oracle_apply(t_o, root_o, dict(deletes))
        root_n = _native_apply(t_n, root_n, dict(deletes))
        assert root_o == root_n
        assert dict(t_o._pending) == dict(t_n._pending)
        # and it really is a single leaf again
        for t, root in ((t_o, root_o), (t_n, root_n)):
            assert t.get(root, keys[survivor]) == b"v%d" % survivor
            assert [kv[1] for kv in t.iter_items(root)] == [
                b"v%d" % survivor
            ]


def test_leaf_split_inside_shard():
    """Keys sharing the first nibble land in one subtrie and split a leaf
    at depth >= 1: the native split chain must match the oracle's."""
    a = _key_with_first_nibble(5, 1)
    b = _key_with_first_nibble(5, 2)
    c = _key_with_first_nibble(9, 3)
    t_o, t_n = Trie(MemoryKV()), _native()
    root_o = _serial_oracle_apply(t_o, EMPTY_ROOT, {a: b"1", c: b"3"})
    root_n = _native_apply(t_n, EMPTY_ROOT, {a: b"1", c: b"3"})
    assert root_o == root_n
    batch = {b: b"2", c: None}
    root_o = _serial_oracle_apply(t_o, root_o, dict(batch))
    root_n = _native_apply(t_n, root_n, dict(batch))
    assert root_o == root_n
    assert dict(t_o._pending) == dict(t_n._pending)


def test_noop_batch_preserves_root_identity():
    """Absent-key deletes and same-value puts are pure no-ops: both walks
    must return the OLD root (the short-circuit that keeps repeated
    emulations from storing duplicate nodes)."""
    rng = random.Random(99)
    writes = {rng.randbytes(8): rng.randbytes(8) for _ in range(40)}
    noop = dict(writes)  # same values
    noop.update({rng.randbytes(9): None for _ in range(20)})  # absent keys
    for t in (Trie(MemoryKV()), _native()):
        root = t.apply_many(EMPTY_ROOT, dict(writes))
        before = dict(t._pending)
        assert t.apply_many(root, dict(noop)) == root
        # no-op application may re-store identical nodes but never new ones
        assert dict(t._pending) == before


def test_defaults_match_real_thresholds():
    """A batch past the native walk's own thresholds (its keys hashed on
    several threads from 4,096 writes, a level's nodes from 64 KiB a
    thread) still agrees with the oracle: the small batches above run
    every walk on one thread."""
    rng = random.Random(123)
    pool = [rng.randbytes(10) for _ in range(12000)]
    t_o, t_n = Trie(MemoryKV()), _native()
    root_o = root_n = EMPTY_ROOT
    for step in range(2):
        writes = _random_batch(rng, pool, 9000, 0.25)
        root_o = _serial_oracle_apply(t_o, root_o, dict(writes))
        root_n = _native_apply(t_n, root_n, dict(writes))
        assert root_o == root_n, step
        assert dict(t_o._pending) == dict(t_n._pending)
