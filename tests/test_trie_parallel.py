"""Merkleization differentials.

The deferred level-batched hashing of `apply_many`, on one thread or on
the hashing threads, must be BIT-IDENTICAL to the immediate-hash walk:
same roots, same node sets, same pending-buffer contents. These tests lock
that with a 200-seed randomized differential over mixed put/delete batches
(leaf splits, single-leaf collapses, collapses at the root) plus targeted
edge cases the fuzz can miss.
"""
import random

import pytest

import lachain_tpu.storage.trie as trie_mod
from lachain_tpu.crypto.hashes import keccak256
from lachain_tpu.storage.kv import MemoryKV
from lachain_tpu.storage.trie import EMPTY_ROOT, Trie

pytestmark = [pytest.mark.trie, pytest.mark.storage]


@pytest.fixture
def low_thresholds(monkeypatch):
    """Drop the defer floor so small randomized batches exercise the
    deferred machinery instead of the immediate walk."""
    monkeypatch.setattr(trie_mod, "MIN_DEFER_OPS", 4)


def _serial_oracle_apply(t: Trie, root: bytes, writes) -> bytes:
    """Immediate per-node hashing (no defer) — the ground truth the
    deferred route must match."""
    entries = {keccak256(k): v for k, v in writes.items()}
    ops = sorted(entries.items())
    return t._bulk(root, ops, 0)


def _threaded_apply(t: Trie, root: bytes, writes) -> bytes:
    """The deferred route with every level sent to the hashing threads."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(trie_mod, "MIN_HASH_THREAD_BYTES", 0)
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
def test_differential_200_seeds(low_thresholds, seed_base):
    """50 seeds per case x 4 cases = 200 randomized workloads: immediate
    oracle vs deferred on one thread vs deferred on the hashing threads:
    roots and pending buffers must be identical."""
    for seed in range(seed_base, seed_base + 50):
        rng = random.Random(seed)
        # small key pool => deletes hit existing keys, repeated puts split
        # and re-split leaves, collapses happen across batches
        pool = [rng.randbytes(rng.randrange(1, 24)) for _ in range(60)]
        t_oracle = Trie(MemoryKV())
        t_defer = Trie(MemoryKV())
        t_thread = Trie(MemoryKV())
        root_o = root_d = root_s = EMPTY_ROOT
        for step in range(3):
            writes = _random_batch(
                rng, pool, rng.randrange(8, 80), rng.choice((0.2, 0.5, 0.8))
            )
            root_o = _serial_oracle_apply(t_oracle, root_o, dict(writes))
            root_d = t_defer.apply_many(root_d, dict(writes))
            root_s = _threaded_apply(t_thread, root_s, dict(writes))
            assert root_o == root_d == root_s, (seed, step)
            assert dict(t_oracle._pending) == dict(t_defer._pending), (
                seed,
                step,
            )
            assert dict(t_oracle._pending) == dict(t_thread._pending), (
                seed,
                step,
            )
        # materialized state agrees too (leaf set, not just hashes)
        if root_o != EMPTY_ROOT:
            assert list(t_oracle.iter_items(root_o)) == list(
                t_thread.iter_items(root_s)
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


def test_single_leaf_collapse_across_subtrie_boundary(low_thresholds):
    """Delete down to ONE live leaf: the root branch must collapse to that
    leaf. In the deferred route the collapse decision is made over
    placeholder tokens of the 16 subtries, hashed only afterwards — the
    seam where it could diverge from the immediate oracle."""
    keys = [_key_with_first_nibble(n, 0) for n in range(16)]
    for survivor in (0, 7, 15):
        t_o, t_s = Trie(MemoryKV()), Trie(MemoryKV())
        base_writes = {k: b"v%d" % i for i, k in enumerate(keys)}
        root_o = _serial_oracle_apply(t_o, EMPTY_ROOT, dict(base_writes))
        root_s = t_s.apply_many(EMPTY_ROOT, dict(base_writes))
        assert root_o == root_s
        # one batch deletes every subtrie but one — 15 of them become
        # EMPTY_ROOT, and the walk must collapse the branch to a leaf
        deletes = {k: None for i, k in enumerate(keys) if i != survivor}
        root_o = _serial_oracle_apply(t_o, root_o, dict(deletes))
        root_s = _threaded_apply(t_s, root_s, dict(deletes))
        assert root_o == root_s
        assert dict(t_o._pending) == dict(t_s._pending)
        # and it really is a single leaf again
        assert t_s.get(root_s, keys[survivor]) == b"v%d" % survivor
        assert [kv[1] for kv in t_s.iter_items(root_s)] == [
            b"v%d" % survivor
        ]


def test_leaf_split_inside_shard(low_thresholds):
    """Keys sharing the first nibble land in one subtrie and split a leaf
    at depth >= 1: the deferred split chain must match the oracle's."""
    a = _key_with_first_nibble(5, 1)
    b = _key_with_first_nibble(5, 2)
    c = _key_with_first_nibble(9, 3)
    t_o, t_s = Trie(MemoryKV()), Trie(MemoryKV())
    root_o = _serial_oracle_apply(t_o, EMPTY_ROOT, {a: b"1", c: b"3"})
    root_s = t_s.apply_many(EMPTY_ROOT, {a: b"1", c: b"3"})
    assert root_o == root_s
    batch = {b: b"2", c: None}
    root_o = _serial_oracle_apply(t_o, root_o, dict(batch))
    root_s = _threaded_apply(t_s, root_s, dict(batch))
    assert root_o == root_s
    assert dict(t_o._pending) == dict(t_s._pending)


def test_noop_batch_preserves_root_identity(low_thresholds):
    """Absent-key deletes and same-value puts are pure no-ops: both fast
    paths must return the OLD root (the short-circuit that keeps repeated
    emulations from storing duplicate nodes)."""
    rng = random.Random(99)
    writes = {rng.randbytes(8): rng.randbytes(8) for _ in range(40)}
    t = Trie(MemoryKV())
    root = t.apply_many(EMPTY_ROOT, dict(writes))
    before = dict(t._pending)
    noop = dict(writes)  # same values
    noop.update({rng.randbytes(9): None for _ in range(20)})  # absent keys
    assert t.apply_many(root, dict(noop)) == root
    assert _threaded_apply(t, root, dict(noop)) == root
    assert _serial_oracle_apply(t, root, dict(noop)) == root
    # no-op application may re-store identical nodes but never new ones
    assert dict(t._pending) == before


def test_defaults_match_real_thresholds():
    """At REAL thresholds a big batch through apply_many still agrees with
    the oracle — guards against the fixture hiding a threshold-dependent
    bug."""
    rng = random.Random(123)
    pool = [rng.randbytes(10) for _ in range(1200)]
    t_o, t_d = Trie(MemoryKV()), Trie(MemoryKV())
    root_o = root_d = EMPTY_ROOT
    for step in range(2):
        writes = _random_batch(rng, pool, 900, 0.25)
        root_o = _serial_oracle_apply(t_o, root_o, dict(writes))
        root_d = t_d.apply_many(root_d, dict(writes))
        assert root_o == root_d, step
        assert dict(t_o._pending) == dict(t_d._pending)
