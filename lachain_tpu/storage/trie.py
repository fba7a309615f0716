"""Content-addressed 16-ary Merkle trie — the authenticated state store.

Parity with the reference's versioned trie
(/root/reference/src/Lachain.Storage/Trie/TrieHashMap.cs:17-180,
InternalNode.cs:1-135, NodeSerializer.cs): 16-ary branching over the nibbles
of keccak256(key) (keys hashed before insert, TrieHashMap.cs:90-98), root
hash == state hash per repository.

Redesign vs the reference: nodes are CONTENT-ADDRESSED (stored by the hash of
their canonical encoding) instead of carrying monotone version ids
(VersionFactory.cs). Structural sharing makes every root a free, immutable
snapshot: "versions" are simply root hashes, which collapses the reference's
Committed/Approved/Pending tier machinery into plain values (state.py) and
makes rollback O(1). An LRU node cache fills the role of TrieHashMap's cache.

Two walkers, which give the same roots and the same stored nodes. A Trie
made with `native=True` (a validator's own state: core/node.py, devnet's
make_node) runs `apply_many` and `get` in C++ (crypto/native/trie.cpp) over
a node table of its own, the process's tables bounded together in bytes
(NODE_TABLE_BYTES): one call walks, builds, encodes and hashes a whole
tree's batch, and one call answers a read. Every other Trie, and a native
one where the library is absent, walks in Python below, hashing each node
as it is stored: the oracle the native walk is tested against, and the
walker the benchmark's reference re-executes the chain on, so that its
state roots are checked by code independent of the native walk.
"""
from __future__ import annotations

import ctypes
import functools
import itertools
import os
import weakref
from array import array
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

from ..crypto.hashes import keccak256
from ..utils import metrics
from ..utils.serialization import Reader, write_bytes, write_u16
from .kv import EntryPrefix, KVStore, prefixed

EMPTY_ROOT = b"\x00" * 32
_NIBBLES = 64  # keccak256 -> 64 nibbles

# the bound on all of a process's native node tables together, each node
# charged its encoding plus 128 bytes; the live tables share it evenly. A
# deployment runs one validator a process, whose table then holds a
# 10,000-transfer block's working set (the balances tree's ~21,000 leaves
# with their interior, and the receipts tree, whose leaves a new key
# splits, growing by ~50,000 stored nodes a block) for 21 blocks before
# the oldest receipts leave; hb64-sim's 64 validators get 4 MiB each
NODE_TABLE_BYTES = 256 << 20
_DELETED = 0xFFFFFFFF  # a value length that marks a delete
# lt_trie_* statuses (trie.cpp); 3 is a bad node encoding
_MISSING, _LOADER_ERROR = 1, 2
_LOADER = ctypes.CFUNCTYPE(
    ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64
)
_native_cache = [False, None]


def native_walks():
    """The library that carries the trie's native walks, or None where the
    hash library is absent (crypto.hashes._native_lib): then a native Trie
    walks in Python. Probed once a process."""
    if not _native_cache[0]:
        _native_cache[0] = True
        from ..crypto.hashes import _native_lib

        lib = _native_lib()
        if lib is not None:
            if not hasattr(lib, "lt_trie_apply"):
                raise RuntimeError(
                    "the native library lacks the trie's walks (lt_trie_*): "
                    "a stale build"
                )
            _bind(lib)
            _native_cache[1] = lib
    return _native_cache[1]


def _bind(lib) -> None:
    vp, u64, u64p = ctypes.c_void_p, ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64)
    lib.lt_trie_new.argtypes = [u64]
    lib.lt_trie_new.restype = vp
    lib.lt_trie_free.argtypes = [vp]
    lib.lt_trie_free.restype = None
    lib.lt_trie_clear.argtypes = [vp]
    lib.lt_trie_clear.restype = None
    lib.lt_trie_counts.argtypes = [vp, u64p]
    lib.lt_trie_counts.restype = None
    lib.lt_trie_size.argtypes = [vp, u64p]
    lib.lt_trie_size.restype = None
    lib.lt_trie_insert.argtypes = [vp, ctypes.c_char_p, u64, ctypes.c_char_p, vp, u64]
    lib.lt_trie_insert.restype = None
    lib.lt_trie_apply.argtypes = [
        vp, ctypes.c_char_p, ctypes.c_char_p, vp, ctypes.c_char_p, vp, u64,
        ctypes.c_uint32, ctypes.c_int, _LOADER, ctypes.c_char_p, u64p,
    ]
    lib.lt_trie_apply.restype = vp
    lib.lt_trie_result_free.argtypes = [vp]
    lib.lt_trie_result_free.restype = None
    lib.lt_trie_get.argtypes = [
        vp, ctypes.c_char_p, ctypes.c_char_p, u64, _LOADER, u64p,
    ]
    lib.lt_trie_get.restype = ctypes.c_int64


def _make_loader(pending: Dict[bytes, bytes], kv: KVStore, errors: list):
    """The native table's one way to a node it lacks: what `_load` reads,
    the write-back buffer first, then the store. Holds the buffer and the
    store, not the Trie, so the table's owner is freed as usual."""
    prefix = prefixed(EntryPrefix.TRIE_NODE)

    def load(h, buf, cap):
        try:
            key = prefix + ctypes.string_at(h, 32)
            enc = pending.get(key)
            if enc is None:
                enc = kv.get(key)
            if enc is None:
                return -1
            if len(enc) <= cap:
                ctypes.memmove(buf, enc, len(enc))
            return len(enc)
        except BaseException as exc:  # noqa: BLE001 - re-raised by the caller
            errors.append(exc)
            return -2

    return _LOADER(load)


@functools.cache
def _host_cores() -> int:
    """The native hashing threads a walk may use: the host's cores, 16 at
    most. Read once a process: os.cpu_count() reads a file, 36-80 us a call
    on the chip's host, and a freeze asks once a subtree."""
    return min(os.cpu_count() or 1, 16)


def _nibble(h: bytes, depth: int) -> int:
    byte = h[depth // 2]
    return (byte >> 4) if depth % 2 == 0 else (byte & 0x0F)


def _group_by_nibble(pairs, depth: int) -> Dict[int, list]:
    """Partition (kh, ...) pairs by their nibble at `depth` — the one
    grouping rule of the bulk walk and the subtree builder."""
    groups: Dict[int, list] = {}
    for kh, v in pairs:
        groups.setdefault(_nibble(kh, depth), []).append((kh, v))
    return groups


@dataclass(frozen=True)
class LeafNode:
    key_hash: bytes  # full 32-byte hashed key
    value: bytes

    def encode(self) -> bytes:
        return b"L" + self.key_hash + write_bytes(self.value)


@dataclass(frozen=True)
class InternalNode:
    # 16 child hashes (EMPTY_ROOT = no child) — mask+list on the wire like the
    # reference's children-mask encoding (InternalNode.cs)
    children: Tuple[bytes, ...]

    def encode(self) -> bytes:
        mask = 0
        present = []
        for i, c in enumerate(self.children):
            if c != EMPTY_ROOT:
                mask |= 1 << i
                present.append(c)
        return b"I" + write_u16(mask) + b"".join(present)


def _decode(data: bytes):
    if data[0:1] == b"L":
        r = Reader(data[33:])
        return LeafNode(key_hash=data[1:33], value=r.bytes_())
    if data[0:1] == b"I":
        mask = int.from_bytes(data[1:3], "big")
        children = []
        off = 3
        for i in range(16):
            if mask & (1 << i):
                children.append(data[off : off + 32])
                off += 32
            else:
                children.append(EMPTY_ROOT)
        return InternalNode(tuple(children))
    raise ValueError("bad trie node encoding")


class Trie:
    """Handle over a KV store; every mutation returns a NEW root hash.

    Node writes are WRITE-BACK buffered: _store fills `_pending` instead of
    issuing a kv.put (which on SqliteKV is an fsynced autocommit — ~40us
    PER NODE, 100k nodes per 10k-tx block). StateManager.commit drains the
    buffer into the same atomic write_batch that persists the roots, so
    nodes are never durable later than a root referencing them — strictly
    better crash ordering than the old eager puts (which leaked orphan
    nodes from uncommitted emulations onto disk)."""

    def __init__(
        self, kv: KVStore, cache_size: int = 65536, native: bool = False
    ):
        self._kv = kv
        self._cache: OrderedDict[bytes, object] = OrderedDict()
        self._cache_size = cache_size
        self._pending: Dict[bytes, bytes] = {}  # prefixed key -> encoding
        # the library of the native walks (None: the Python walker), and
        # the node table and its loader, made on the first native walk
        self._walks = native_walks() if native else None
        self._table: Optional[int] = None
        self._loader = None
        self._loader_errors: list = []

    # -- node io -------------------------------------------------------------
    def _store(self, node) -> bytes:
        enc = node.encode()
        h = keccak256(enc)
        self._pending[prefixed(EntryPrefix.TRIE_NODE, h)] = enc
        self._cache_put(h, node)
        return h

    def _load(self, h: bytes):
        node = self._cache.get(h)
        if node is not None:
            self._cache.move_to_end(h)
            return node
        key = prefixed(EntryPrefix.TRIE_NODE, h)
        enc = self._pending.get(key)
        if enc is None:
            enc = self._kv.get(key)
        if enc is None:
            raise KeyError(f"missing trie node {h.hex()}")
        node = _decode(enc)
        self._cache_put(h, node)
        return node

    def peek_pending(self) -> List[Tuple[bytes, bytes]]:
        """The buffered node writes, for the caller's write_batch. Includes
        nodes from discarded emulations (the eager-write design persisted
        those too; shrink reclaims them). The buffer is NOT cleared here —
        call confirm_pending with these items only after the batch is
        durable, so a failed commit keeps the sole copy of the nodes."""
        return list(self._pending.items())

    def confirm_pending(self, items: List[Tuple[bytes, bytes]]) -> None:
        """Drop buffered writes that a successful write_batch persisted."""
        for k, _ in items:
            self._pending.pop(k, None)

    def export_pending(self) -> Dict[bytes, bytes]:
        """Snapshot of the buffered node writes, for replaying into another
        trie over the SAME chain (cross-validator emulation sharing): nodes
        are content-addressed, so absorbing a snapshot taken after an
        identical state transition hands the consumer exactly the nodes its
        own freeze would have buffered."""
        return dict(self._pending)

    def absorb_pending(self, nodes: Dict[bytes, bytes]) -> None:
        """Adopt another trie's exported node buffer (see export_pending).
        Re-absorbing an already-persisted node is harmless — same key, same
        encoding — it just rides the next commit batch again. The native
        table takes the nodes too, so the next walk finds them warm."""
        self._pending.update(nodes)
        if self._walks is None or not nodes:
            return
        keys = b"".join(nodes)
        # every key is the 2-byte prefix and a 32-byte hash, or none is fed
        if len(keys) == 34 * len(nodes):
            encs = list(nodes.values())
            offs = array("Q", [0])
            offs.extend(itertools.accumulate(map(len, encs)))
            self._walks.lt_trie_insert(
                self._native_table(), keys, 34,
                b"".join(encs), offs.buffer_info()[0], len(encs),
            )

    def clear_cache(self) -> None:
        self._cache.clear()
        if self._table is not None:
            self._walks.lt_trie_clear(self._table)

    # -- the native walks ----------------------------------------------------
    def _native_table(self) -> int:
        if self._table is None:
            lib = self._walks
            self._table = lib.lt_trie_new(NODE_TABLE_BYTES)
            weakref.finalize(self, lib.lt_trie_free, self._table)
            self._loader = _make_loader(
                self._pending, self._kv, self._loader_errors
            )
        return self._table

    def _flush_loads(self) -> None:
        """Fold the table's counts into trie_node_loads_total{source}: nodes
        a native walk or read found in the table, and nodes it fetched
        through the loader, since the last fold."""
        counts = (ctypes.c_uint64 * 2)()
        self._walks.lt_trie_counts(self._table, counts)
        if counts[0]:
            metrics.inc("trie_node_loads_total", counts[0], {"source": "table"})
        if counts[1]:
            metrics.inc("trie_node_loads_total", counts[1], {"source": "store"})

    def _native_failed(self, status: int, missing: bytes):
        if status == _MISSING:
            raise KeyError(f"missing trie node {missing.hex()}")
        if status == _LOADER_ERROR:
            raise self._loader_errors.pop()
        raise ValueError("bad trie node encoding")

    def _apply_native(
        self, root: bytes, writes: Dict[bytes, Optional[bytes]]
    ) -> bytes:
        """apply_many in one native call: it hashes the keys, walks,
        builds, encodes and hashes every node, and hands back the stored
        nodes for the write-back buffer."""
        lib = self._walks
        table = self._native_table()
        vals = list(writes.values())
        klens = array("I", map(len, writes))
        vlens = array("I", [_DELETED if v is None else len(v) for v in vals])
        out_root = ctypes.create_string_buffer(32)
        info = (ctypes.c_uint64 * 6)()
        res = lib.lt_trie_apply(
            table, root, b"".join(writes), klens.buffer_info()[0],
            b"".join([v for v in vals if v is not None]),
            vlens.buffer_info()[0], len(vals), int(EntryPrefix.TRIE_NODE),
            _host_cores(), self._loader, out_root, info,
        )
        try:
            status, n, keys_at, encs_at, offs_at, size = info
            if status:
                self._native_failed(status, out_root.raw)
            kb = ctypes.string_at(keys_at, 34 * n)
            eb = ctypes.string_at(encs_at, size)
            offs = array("Q")
            offs.frombytes(ctypes.string_at(offs_at, 8 * (n + 1)))
        finally:
            lib.lt_trie_result_free(res)
            self._flush_loads()
        keys = map(kb.__getitem__, map(slice, range(0, 34 * n, 34), range(34, 34 * n + 1, 34)))
        encs = map(eb.__getitem__, map(slice, offs, offs[1:]))
        self._pending.update(zip(keys, encs))
        metrics.inc("trie_nodes_hashed_total", n)
        return out_root.raw

    def _get_native(self, root: bytes, key: bytes) -> Optional[bytes]:
        info = (ctypes.c_uint64 * 2)()
        n = self._walks.lt_trie_get(
            self._native_table(), root, key, len(key), self._loader, info
        )
        if info[1]:  # a store load: counted now, table hits at the next fold
            self._flush_loads()
        if n >= 0:
            return ctypes.string_at(info[0], n)
        if n == -1:
            return None
        self._native_failed(-1 - n, ctypes.string_at(info[0], 32))

    def _cache_put(self, h: bytes, node) -> None:
        self._cache[h] = node
        if len(self._cache) > self._cache_size:
            self._cache.popitem(last=False)

    # -- public api ----------------------------------------------------------
    def get(self, root: bytes, key: bytes) -> Optional[bytes]:
        if root == EMPTY_ROOT:
            return None
        if self._walks is not None:
            return self._get_native(root, key)
        kh = keccak256(key)
        node_hash = root
        depth = 0
        while True:
            node = self._load(node_hash)
            if isinstance(node, LeafNode):
                return node.value if node.key_hash == kh else None
            nxt = node.children[_nibble(kh, depth)]
            if nxt == EMPTY_ROOT:
                return None
            node_hash = nxt
            depth += 1

    def put(self, root: bytes, key: bytes, value: bytes) -> bytes:
        kh = keccak256(key)
        return self._put_hashed(root, kh, value, 0)

    def _put_hashed(self, node_hash: bytes, kh: bytes, value: bytes, depth: int) -> bytes:
        if node_hash == EMPTY_ROOT:
            return self._store(LeafNode(kh, value))
        node = self._load(node_hash)
        if isinstance(node, LeafNode):
            if node.key_hash == kh:
                return self._store(LeafNode(kh, value))
            # split: push the existing leaf down until paths diverge
            children = [EMPTY_ROOT] * 16
            old_nib = _nibble(node.key_hash, depth)
            new_nib = _nibble(kh, depth)
            if old_nib == new_nib:
                sub = self._put_hashed(
                    self._store(node), kh, value, depth + 1
                )
                children[old_nib] = sub
            else:
                children[old_nib] = self._store(node)
                children[new_nib] = self._store(LeafNode(kh, value))
            return self._store(InternalNode(tuple(children)))
        nib = _nibble(kh, depth)
        new_child = self._put_hashed(node.children[nib], kh, value, depth + 1)
        children = list(node.children)
        children[nib] = new_child
        return self._store(InternalNode(tuple(children)))

    def delete(self, root: bytes, key: bytes) -> bytes:
        kh = keccak256(key)
        new_root = self._del_hashed(root, kh, 0)
        return new_root if new_root is not None else root

    def _collapse_or_store(self, children) -> bytes:
        """Store an internal node, applying THE canonical collapse rule
        (single shared copy: the bulk and sequential paths must collapse
        identically or their roots diverge): an empty child set dissolves,
        a single live LEAF child replaces the branch."""
        live = [c for c in children if c != EMPTY_ROOT]
        if not live:
            return EMPTY_ROOT
        if len(live) == 1:
            only = self._load(live[0])
            if isinstance(only, LeafNode):
                return self._store(only)
        return self._store(InternalNode(tuple(children)))

    # -- bulk application ----------------------------------------------------
    # The tree is CANONICAL in its leaf set (inserts create internal chains
    # exactly along shared prefixes; deletes collapse single-leaf branches
    # all the way back up), so applying a batch bottom-up produces the same
    # root as replaying the keys one at a time — while rebuilding each
    # shared internal node ONCE per block instead of once per key. This is
    # the block-commit hot path: at N=64 the per-key replay was ~18% of the
    # whole simulated era.

    def apply_many(
        self, root: bytes, writes: Dict[bytes, Optional[bytes]]
    ) -> bytes:
        """Apply a {key: value-or-None(delete)} batch; returns the new root
        (bit-identical to sequential put/delete in any order).

        On a native Trie one native call does it all (_apply_native);
        otherwise the Python walk below, each node hashed as it is
        stored."""
        if not writes:
            return root
        if self._walks is not None:
            return self._apply_native(root, writes)
        entries: Dict[bytes, Optional[bytes]] = {
            keccak256(k): v for k, v in writes.items()
        }
        return self._bulk(root, sorted(entries.items()), 0)

    def _bulk(self, node_hash: bytes, ops, depth: int) -> bytes:
        if not ops:
            return node_hash
        if node_hash == EMPTY_ROOT:
            leaves = [(kh, v) for kh, v in ops if v is not None]
            return self._build_subtree(leaves, depth)
        node = self._load(node_hash)
        if isinstance(node, LeafNode):
            merged = dict(ops)
            if node.key_hash not in merged:
                merged[node.key_hash] = node.value
            leaves = sorted(
                (kh, v) for kh, v in merged.items() if v is not None
            )
            if leaves == [(node.key_hash, node.value)]:
                return node_hash  # no-op batch over this leaf
            return self._build_subtree(leaves, depth)
        children = list(node.children)
        groups = _group_by_nibble(ops, depth)
        for nib, group in groups.items():
            children[nib] = self._bulk(children[nib], group, depth + 1)
        if children == list(node.children):
            # nothing changed under us (absent-key deletes / same-value
            # puts): a pure no-op, like sequential delete of a missing key
            return node_hash
        return self._collapse_or_store(children)

    def _build_subtree(self, leaves, depth: int) -> bytes:
        """Canonical subtree for sorted (kh, value) leaves on empty ground."""
        if not leaves:
            return EMPTY_ROOT
        if len(leaves) == 1:
            kh, v = leaves[0]
            return self._store(LeafNode(kh, v))
        children = [EMPTY_ROOT] * 16
        for nib, group in _group_by_nibble(leaves, depth).items():
            children[nib] = self._build_subtree(group, depth + 1)
        return self._store(InternalNode(tuple(children)))

    def _del_hashed(self, node_hash: bytes, kh: bytes, depth: int) -> Optional[bytes]:
        """Returns the new subtree hash, EMPTY_ROOT if emptied, or None if
        the key was absent (no change)."""
        if node_hash == EMPTY_ROOT:
            return None
        node = self._load(node_hash)
        if isinstance(node, LeafNode):
            return EMPTY_ROOT if node.key_hash == kh else None
        nib = _nibble(kh, depth)
        sub = self._del_hashed(node.children[nib], kh, depth + 1)
        if sub is None:
            return None
        children = list(node.children)
        children[nib] = sub
        return self._collapse_or_store(children)

    def iter_items(self, root: bytes) -> Iterator[Tuple[bytes, bytes]]:
        """All (hashed_key, value) pairs under a root (ordered by key hash)."""
        if root == EMPTY_ROOT:
            return
        stack = [root]
        while stack:
            node = self._load(stack.pop())
            if isinstance(node, LeafNode):
                yield node.key_hash, node.value
            else:
                for c in reversed(node.children):
                    if c != EMPTY_ROOT:
                        stack.append(c)

    def node_count(self, root: bytes) -> int:
        if root == EMPTY_ROOT:
            return 0
        seen = set()
        stack = [root]
        while stack:
            h = stack.pop()
            if h in seen:
                continue
            seen.add(h)
            node = self._load(h)
            if isinstance(node, InternalNode):
                stack.extend(c for c in node.children if c != EMPTY_ROOT)
        return len(seen)
