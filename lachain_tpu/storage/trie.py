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
"""
from __future__ import annotations

import functools
import os
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

from ..crypto.hashes import keccak256, keccak256_batch
from ..utils import metrics
from ..utils.serialization import Reader, write_bytes, write_u16
from .kv import EntryPrefix, KVStore, prefixed

EMPTY_ROOT = b"\x00" * 32
_NIBBLES = 64  # keccak256 -> 64 nibbles

# batch-size floor for deferred level-batched hashing: below it the
# bookkeeping costs more than the per-node keccak dispatch it saves
MIN_DEFER_OPS = 32
# a level's encodings go to native hashing threads only from this many
# bytes on; below it the threads' start costs more than they hash
MIN_HASH_THREAD_BYTES = 3 << 19

_KECCAK_BATCH_BUCKETS = (16, 64, 256, 1024, 4096, 16384, 65536)


@functools.cache
def _host_cores() -> int:
    """The native hashing threads a level may use: the host's cores, 16 at
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


class _DeferredHasher:
    """Deferred-hash node sink for bulk merkleization: while armed on a
    Trie, `_store` hands out a 9-byte placeholder token instead of hashing
    the node. `Trie._resolve_deferred` then encodes the accumulated nodes
    level-by-level bottom-up, hashes each level's encodings in ONE native
    batch call (crypto.hashes.keccak256_batch) and patches child
    references — collapsing ~one Python→C keccak crossing per node into
    one per tree level (~6 for a 100k-node block).

    Token contract (what keeps `_bulk`'s no-op short-circuits and the
    collapse rules bit-identical to the immediate-hash path): a token is
    never equal to a real 32-byte hash, to EMPTY_ROOT, or to a different
    token, and tokens are handed out only for genuinely stored nodes — so
    `children == list(node.children)` still means exactly "nothing changed
    under this branch"."""

    __slots__ = ("nodes", "levels", "buckets", "count")
    PREFIX = 0xFE

    def __init__(self):
        self.nodes: Dict[bytes, object] = {}  # token -> node (for _load)
        self.levels: Dict[bytes, int] = {}  # token -> bottom-up level
        # per-level (tokens, nodes) parallel lists — the batch-hash units
        self.buckets: List[Tuple[List[bytes], List[object]]] = []
        self.count = 0

    def store(self, node) -> bytes:
        # HOT: once per stored node. The level is known right here —
        # children are always stored before their parent — so computing
        # it now saves _resolve_deferred a whole extra pass. b"\xfe" ==
        # PREFIX inlined; token child refs are the only 9-byte refs.
        token = b"\xfe" + self.count.to_bytes(8, "big")
        self.count += 1
        lvl = 0
        if type(node) is InternalNode:
            levels = self.levels
            for c in node.children:
                if len(c) == 9:
                    cl = levels[c]
                    if cl >= lvl:
                        lvl = cl + 1
        self.levels[token] = lvl
        self.nodes[token] = node
        buckets = self.buckets
        if lvl >= len(buckets):  # parents are at most one level above
            buckets.append(([], []))
        bt, bn = buckets[lvl]
        bt.append(token)
        bn.append(node)
        return token

    @staticmethod
    def is_token(h: bytes) -> bool:
        # real node hashes are 32 bytes; tokens are 9
        return len(h) == 9 and h[0] == _DeferredHasher.PREFIX


class Trie:
    """Handle over a KV store; every mutation returns a NEW root hash.

    Node writes are WRITE-BACK buffered: _store fills `_pending` instead of
    issuing a kv.put (which on SqliteKV is an fsynced autocommit — ~40us
    PER NODE, 100k nodes per 10k-tx block). StateManager.commit drains the
    buffer into the same atomic write_batch that persists the roots, so
    nodes are never durable later than a root referencing them — strictly
    better crash ordering than the old eager puts (which leaked orphan
    nodes from uncommitted emulations onto disk)."""

    def __init__(self, kv: KVStore, cache_size: int = 65536):
        self._kv = kv
        self._cache: OrderedDict[bytes, object] = OrderedDict()
        self._cache_size = cache_size
        self._pending: Dict[bytes, bytes] = {}  # prefixed key -> encoding
        # armed deferred-hash sink (apply_many's deferred route only)
        self._defer: Optional[_DeferredHasher] = None

    # -- node io -------------------------------------------------------------
    def _store(self, node) -> bytes:
        if self._defer is not None:
            return self._defer.store(node)
        enc = node.encode()
        h = keccak256(enc)
        self._pending[prefixed(EntryPrefix.TRIE_NODE, h)] = enc
        self._cache_put(h, node)
        return h

    def _load(self, h: bytes):
        if self._defer is not None and _DeferredHasher.is_token(h):
            return self._defer.nodes[h]
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
        encoding — it just rides the next commit batch again."""
        self._pending.update(nodes)

    def clear_cache(self) -> None:
        self._cache.clear()

    def _cache_put(self, h: bytes, node) -> None:
        self._cache[h] = node
        if len(self._cache) > self._cache_size:
            self._cache.popitem(last=False)

    def _trim_cache(self) -> None:
        """One bulk trim after a bulk update, instead of per-put LRU churn
        (recency inside one batch is meaningless anyway)."""
        cache = self._cache
        while len(cache) > self._cache_size:
            cache.popitem(last=False)

    # -- public api ----------------------------------------------------------
    def get(self, root: bytes, key: bytes) -> Optional[bytes]:
        if root == EMPTY_ROOT:
            return None
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

        One walker; the batch picks how it hashes:
          * below MIN_DEFER_OPS each node is hashed as it is stored;
          * from MIN_DEFER_OPS on, nodes are encoded level-by-level
            bottom-up and each level is hashed in one native keccak call
            (_resolve_deferred: on the host's cores only from
            MIN_HASH_THREAD_BYTES a level)."""
        if not writes:
            return root
        entries: Dict[bytes, Optional[bytes]] = {
            keccak256(k): v for k, v in writes.items()
        }
        ops = sorted(entries.items())
        if len(ops) < MIN_DEFER_OPS:
            return self._bulk(root, ops, 0)
        self._defer = _DeferredHasher()
        try:
            out = self._bulk(root, ops, 0)
        finally:
            defer, self._defer = self._defer, None
        resolved = self._resolve_deferred(defer)
        if _DeferredHasher.is_token(out):
            out = resolved[out]
        return out

    def _resolve_deferred(self, defer: _DeferredHasher) -> Dict[bytes, bytes]:
        """Hash a deferred sink's nodes level-by-level bottom-up through
        the native batch keccak, patching child tokens with the hashes of
        the level below, into the pending buffer; returns token -> hash.
        A level goes to the host's cores (_host_cores) only where it
        carries MIN_HASH_THREAD_BYTES of encodings; a smaller one is hashed
        on the calling thread.

        HOT PATH: ~one iteration per node per 10k-tx block commit. Token
        tests are inlined as `len(c) == 9` (real child refs are always 32
        bytes) and leaves — the bulk of every batch — skip the patch
        machinery entirely; the Python bookkeeping here must stay well
        under the per-node ctypes crossing it saves, or deferral is a
        net loss on one core."""
        resolved: Dict[bytes, bytes] = {}
        trie_node = int(EntryPrefix.TRIE_NODE).to_bytes(2, "big")
        pending = self._pending
        cache = self._cache
        for tokens, bnodes in defer.buckets:
            patched: List[object] = []
            for n in bnodes:
                if type(n) is InternalNode:
                    ch = n.children
                    for c in ch:
                        if len(c) == 9:
                            n = InternalNode(
                                tuple(
                                    [
                                        resolved[c] if len(c) == 9 else c
                                        for c in ch
                                    ]
                                )
                            )
                            break
                patched.append(n)
            encs = [n.encode() for n in patched]
            big = sum(map(len, encs)) >= MIN_HASH_THREAD_BYTES
            hashes = keccak256_batch(encs, _host_cores() if big else 1)
            metrics.observe_hist(  # lint-allow: metric-name dimensionless batch-size distribution
                "trie_keccak_batch_size",
                len(encs),
                buckets=_KECCAK_BATCH_BUCKETS,
            )
            # bulk C-level stores instead of a per-node interpreted loop
            keys = [trie_node + h for h in hashes]
            pending.update(zip(keys, encs))
            resolved.update(zip(tokens, hashes))
            cache.update(zip(hashes, patched))
        self._trim_cache()
        metrics.inc("trie_nodes_hashed_total", len(resolved))
        return resolved

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
