"""Transaction pool (mempool).

Parity with the reference's TransactionPool
(/root/reference/src/Lachain.Core/Blockchain/Pool/TransactionPool.cs):
  * Add: signature verify + nonce bookkeeping + persistence (130-148)
  * Peek: fee-ordered proposal sampling with per-sender nonce continuity
    (401+; NonceCalculator.cs:21)
  * Restore from the persistent repo on startup (98+)
  * eviction of included/stale transactions

Admission is SHARDED: the pool's maps are split across `_N_SHARDS`
independent lock domains keyed by the sender address, so concurrent
`add()` calls from the RPC/gossip ingest threads only serialize when two
transactions share a sender shard. The expensive step — ECDSA sender
recovery — runs OUTSIDE every lock. `txpool_admit_lock_wait_seconds`
histograms the time an admitting thread spends blocked on its shard lock,
which is the direct measure of residual admission contention.

The pool is INDEXED: a shard keeps each sender's transactions as one
nonce-ordered chain, every entry with its fee key computed once at
admission, and the pool keeps a hash -> sender map. Proposal, eviction and
sanitize then cost what they touch: one state nonce read per pooled sender
(`txpool_state_nonce_reads_total` counts them), not one per pooled
transaction, and a hash names its shard without a probe of all of them.

The crash-restore repository is written ONCE per eviction call: a call
forgets its transactions in memory under the shard locks, then hands their
keys to the store as one `write_batch` with no shard lock held
(`_delete_rows`), so a block's eviction waits for one WAL fsync, not one a
transaction. `txpool_evict_writes_total` counts those writes.

An ADMISSION's row is durable before anything acknowledges the admission,
and who waits for its fsync depends on who owns the pool:

  * a pool that stands alone (the devnet, the crash workload, a test) has
    nobody after `add` to wait, so `add` writes the row with the store's
    synchronous `put` (a fee replacement: one `write_batch` of the new
    row and the old one's delete) and the row is durable when `add`
    returns;
  * an owner that acknowledges admissions at a boundary of its own
    (`core/node.Node`: a frame to a peer, an RPC answer) takes
    `frame_barrier()`. Where the store's WAL really overlaps
    (`kv.supports_async_batches`: LsmKV) `add` then only SUBMITS the same
    puts and deletes (`write_batch_async`, still under the shard's lock,
    so a hash's put is ahead of any later delete of it in the WAL) and
    keeps the newest ticket; `barrier()` waits for it. The node runs the
    barrier in front of every write to a socket, beside the consensus
    journal's (`network/worker.durable_before_wire`), and the RPC service
    in front of the answer to a submission, off the event loop. The
    node's thread waits for one fsync a frame, not one a transaction,
    while the WAL writer group-commits the rows between. On a store
    without an overlapping WAL the calls stay the synchronous ones.
    `tools/check_invariants.py` rule P holds the submit to both waits.

`txpool_admit_rows_total`, `txpool_admit_waits_total` and
`txpool_admit_store_seconds_total` count the submitted rows, the waits that
had a ticket and the seconds of both; a wait is a span `pool.barrier`.

Lock ordering: shard lock -> `_nonce_lock` (state-trie nonce reads; the
trie's LRU cache is not thread-safe). No path acquires two shard locks
at once, so there is no cross-shard ordering to get wrong. `peek` copies
each shard's chains under its lock; its nonce reads and the merge run
outside every shard lock.
"""
from __future__ import annotations

import heapq
import random
import threading
import time
from bisect import bisect_left
from typing import Callable, Dict, List, Optional, Set, Tuple

from ..storage.crashpoints import crash_point
from ..storage.kv import EntryPrefix, KVStore, prefixed
from ..utils import metrics, tracing, txtrace
from .execution import get_nonce
from .types import SignedTransaction

_N_SHARDS = 16

# shard-lock waits are sub-microsecond uncontended; buckets resolve the
# interesting range (lock convoy under ingest bursts)
_LOCK_WAIT_BUCKETS = (1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1)

# byte-wise 255 - b: among equal fees the LARGER hash is proposed first
_INVERT = bytes(255 - b for b in range(256))

# (nonce, (-gas_price, inverted hash), hash, tx). A chain is a list of
# these in nonce order; nonces are unique within it, so `(nonce,)` bisects
# it and no comparison ever reaches the transaction.
_Entry = Tuple[int, Tuple[int, bytes], bytes, SignedTransaction]


class StateNonces:
    """The pool's nonce reader over a StateManager: account nonces of the
    committed state. `version()` is the committed roots object, which every
    commit and rollback replaces; a pool whose reader has one keeps a
    sender's nonce until that identity changes, so a commit by any path
    (block producer, synchronizer) is seen by the very next read."""

    def __init__(self, state) -> None:
        self._state = state

    def __call__(self, addr: bytes) -> int:
        return get_nonce(self._state.new_snapshot(), addr)

    def version(self) -> object:
        return self._state.committed


class _PoolShard:
    """One lock domain: the slice of the pool whose senders hash here."""

    __slots__ = ("lock", "txs", "chains")

    def __init__(self) -> None:
        self.lock = threading.RLock()
        self.txs: Dict[bytes, SignedTransaction] = {}
        # sender -> its pooled txs in nonce order (reference
        # TransactionHashTrackerByNonce); never left empty
        self.chains: Dict[bytes, List[_Entry]] = {}


class TransactionPool:
    def __init__(
        self,
        kv: KVStore,
        chain_id: int,
        account_nonce: Callable[[bytes], int],
        min_gas_price: int = 1,
    ):
        self._kv = kv
        self.chain_id = chain_id
        self._account_nonce_fn = account_nonce
        self.min_gas_price = min_gas_price
        self._shards = [_PoolShard() for _ in range(_N_SHARDS)]
        # tx hash -> sender, over all shards: written under the sender's
        # shard lock, read without one (dedup, and to name a hash's shard)
        self._sender_of: Dict[bytes, bytes] = {}
        # state-trie nonce reads go through the trie's LRU cache, which is
        # not safe under concurrent mutation — serialize them
        self._nonce_lock = threading.Lock()
        # nonces read at `_nonce_version` (see StateNonces); under _nonce_lock
        self._nonce_version: object = None
        self._nonce_memo: Dict[bytes, int] = {}
        # True once an owner took frame_barrier() on a store whose WAL
        # overlaps: add() submits its row and barrier() waits for it
        self._submit_rows = False
        # under _ticket_lock: the newest write_batch_async ticket not yet
        # waited for (tickets are WAL sequences: the newest covers every
        # earlier one), the rows submitted so far, and how many of them a
        # barrier has waited for
        self._ticket_lock = threading.Lock()
        self._ticket: Optional[int] = None
        self._rows_submitted = 0
        self._rows_durable = 0

    def _shard(self, sender: bytes) -> _PoolShard:
        return self._shards[sender[0] % _N_SHARDS]

    def _account_nonce(self, sender: bytes) -> int:
        with self._nonce_lock:
            version_of = getattr(self._account_nonce_fn, "version", None)
            if version_of is None:
                metrics.inc("txpool_state_nonce_reads_total")
                return self._account_nonce_fn(sender)
            version = version_of()
            if version is not self._nonce_version:
                self._nonce_version = version
                self._nonce_memo = {}
            nonce = self._nonce_memo.get(sender)
            if nonce is None:
                metrics.inc("txpool_state_nonce_reads_total")
                nonce = self._nonce_memo[sender] = self._account_nonce_fn(sender)
            return nonce

    def __len__(self) -> int:
        return len(self._sender_of)

    # -- ingress --------------------------------------------------------------
    def precheck(self, stx: SignedTransaction) -> bool:
        """The cheap admission checks only (dedup + gas floor) — no
        signature recovery. Bulk-ingest callers filter through this BEFORE
        paying for batch sender recovery, so re-gossiped duplicates cost a
        hash lookup, not an ECDSA recover. Advisory by design (add()
        re-checks under the shard lock), so the dict probe runs lock-free."""
        if stx.tx.gas_price < self.min_gas_price:
            return False
        return stx.hash() not in self._sender_of

    def add(self, stx: SignedTransaction) -> bool:
        """Verify + admit. Returns False (and drops) on any rule violation."""
        h = stx.hash()
        if stx.tx.gas_price < self.min_gas_price:
            return False
        if h in self._sender_of:
            return False  # lock-free dedup; re-checked under the shard lock
        # ECDSA recovery is the expensive step — outside every lock
        sender = stx.sender(self.chain_id)
        if sender is None:
            return False
        shard = self._shard(sender)
        nonce = stx.tx.nonce
        t0 = time.perf_counter()
        with shard.lock:
            metrics.observe_hist(
                "txpool_admit_lock_wait_seconds",
                time.perf_counter() - t0,
                buckets=_LOCK_WAIT_BUCKETS,
            )
            if h in shard.txs:
                return False
            if nonce < self._account_nonce(sender):
                return False  # already used
            entry = (nonce, (-stx.tx.gas_price, h.translate(_INVERT)), h, stx)
            replaced: List[bytes] = []
            chain = shard.chains.get(sender)
            if chain is None:
                shard.chains[sender] = [entry]
            else:
                i = bisect_left(chain, (nonce,))
                if i < len(chain) and chain[i][0] == nonce:
                    # replacement only for strictly higher fee
                    old = chain[i]
                    if stx.tx.gas_price <= old[3].tx.gas_price:
                        return False
                    replaced.append(self._forget(shard, old[2]))
                    chain[i] = entry
                else:
                    chain.insert(i, entry)
            shard.txs[h] = stx
            self._sender_of[h] = sender
            # the pool's crash window: admitted to memory, its row not yet
            # durable in the crash-restore repository — a kill here, or
            # after a submit and before the barrier, loses the tx from the
            # restart (best-effort by design; gossip re-fills). Nothing has
            # acknowledged it: submitted, durable before anything does
            crash_point("pool.save.mid")
            key = prefixed(EntryPrefix.POOL_TX, h)
            if self._submit_rows:
                self._submit_row(key, stx.encode(), replaced)
            elif replaced:
                # one atomic write: the repository never holds both rows of
                # the nonce, and never neither
                self._kv.write_batch([(key, stx.encode())], replaced)
            else:
                self._kv.put(key, stx.encode())
        # tx lifecycle stamp OUTSIDE the shard lock (admission succeeded;
        # sampled-only, first stamp wins across gossip re-admissions)
        txtrace.stamp(h, "pool")
        return True

    def _submit_row(self, key: bytes, row: bytes, replaced: List[bytes]) -> None:
        """The repository half of an admission whose owner waits at its own
        boundary: the same puts and deletes as one atomic batch, submitted
        to the store's WAL writer and not waited for. Caller holds the
        shard's lock, which orders this put before any later delete of the
        key in the WAL (`_delete_rows` relies on it)."""
        t0 = time.perf_counter()
        # the loop's part `pool_store` covers what the counter below times,
        # so that the node's `pool_admit` is the admission without it
        with tracing.account("pool_store"):
            ticket = self._kv.write_batch_async([(key, row)], replaced)
            with self._ticket_lock:
                if self._ticket is None or ticket > self._ticket:
                    self._ticket = ticket
                self._rows_submitted += 1
        metrics.inc("txpool_admit_rows_total")
        metrics.inc(
            "txpool_admit_store_seconds_total", time.perf_counter() - t0
        )

    def barrier(self) -> None:
        """Return once the row of every admission so far is durable. With
        no ticket pending it returns at once, without a call into the KV.
        Safe from any thread: the node's loop in front of a frame, an
        executor thread in front of an RPC answer."""
        if self._ticket is None:
            return
        with self._ticket_lock:
            ticket, submitted = self._ticket, self._rows_submitted
            rows = submitted - self._rows_durable
        if ticket is None:
            return
        t0 = time.perf_counter()
        with tracing.account("pool_store"), tracing.span(
            "pool.barrier", "pool", rows=rows
        ):
            # forgotten only once durable: a barrier that raises (a failed
            # WAL) leaves the ticket, so no later frame or answer passes on
            # an empty one
            self._kv.write_barrier(ticket)
        with self._ticket_lock:
            if self._ticket == ticket:
                self._ticket = None
            self._rows_durable = max(self._rows_durable, submitted)
        metrics.inc("txpool_admit_waits_total")
        metrics.inc(
            "txpool_admit_store_seconds_total", time.perf_counter() - t0
        )

    def rows_pending(self) -> bool:
        """Whether barrier() would wait: an admission submitted its row and
        nothing has waited for it since."""
        return self._ticket is not None

    def frame_barrier(self) -> Callable[[], None]:
        """Hand the wait to an owner that acknowledges admissions at a
        boundary of its own: it runs the returned hook before every frame
        and every answer leaves, and add() stops waiting itself — where the
        store has a WAL to submit to; elsewhere add() keeps its synchronous
        calls and the hook never finds a ticket."""
        self._submit_rows = bool(
            getattr(self._kv, "supports_async_batches", False)
        )
        return self.barrier

    # -- proposal --------------------------------------------------------------
    def next_nonce(self, sender: bytes) -> int:
        """Next usable nonce for `sender`: the account nonce advanced past
        any consecutive pending transactions already in the pool."""
        shard = self._shard(sender)
        with shard.lock:
            nonce = self._account_nonce(sender)
            chain = shard.chains.get(sender, ())
            i = bisect_left(chain, (nonce,))
            while i < len(chain) and chain[i][0] == nonce:
                nonce += 1
                i += 1
            return nonce

    def peek(
        self,
        max_txs: int,
        rng: Optional["random.Random"] = None,
        window_txs: Optional[int] = None,
        exclude: Optional[Set[bytes]] = None,
        nonce_override: Optional[Dict[bytes, int]] = None,
    ) -> List[SignedTransaction]:
        """Fee-ordered proposal with per-sender nonce continuity.

        With `rng`, the proposal is a RANDOM sample from a fee-ordered
        window of up to `window_txs` executable txs (the reference's
        RandomSamplingQueue role, Containers/RandomSamplingQueue.cs):
        HoneyBadger blocks carry the UNION of n proposals, so diversity
        across validators — not identical top-fee picks — is what fills
        blocks. The window must therefore span a whole BLOCK's worth of
        txs, not one proposal's worth: n validators sampling 4*max_txs
        txs can union to at most 4*max_txs distinct entries. Sampling
        keeps per-sender nonce chains contiguous by sampling SENDERS,
        then taking their chain prefixes.

        `exclude` / `nonce_override` are the pipelined-proposal overlay:
        when proposing on top of in-flight (decided but uncommitted) blocks,
        the caller masks txs already claimed by those blocks and advances
        the per-sender chain start past their nonces — state reads still
        see the committed trie, which is exactly the sequential outcome
        once the in-flight blocks land."""
        with tracing.span("pool.peek", "pool", size=len(self)):
            chains = self._executable_chains(exclude, nonce_override)
            if rng is None:
                return [e[3] for _, e in _merge(chains, max_txs)]
            window = window_txs if window_txs is not None else 4 * max_txs
            executable = sum(len(c) for c in chains)
            if min(executable, window) <= max_txs:
                return [e[3] for _, e in _merge(chains, window)]
            if executable <= window:
                # the window holds every executable tx: senders first
                # appear in it in the order of their chain heads' keys,
                # each with its whole chain — no merge needed to know that
                sampled = sorted(chains, key=lambda c: c[0][1])
            else:
                in_window: Dict[int, List[_Entry]] = {}
                for j, e in _merge(chains, window):
                    in_window.setdefault(j, []).append(e)
                sampled = list(in_window.values())
            rng.shuffle(sampled)
            picked: List[SignedTransaction] = []
            for chain in sampled:
                take = min(len(chain), max_txs - len(picked))
                picked.extend(e[3] for e in chain[:take])
                if len(picked) >= max_txs:
                    break
            return picked

    def _executable_chains(
        self,
        exclude: Optional[Set[bytes]],
        nonce_override: Optional[Dict[bytes, int]],
    ) -> List[List[_Entry]]:
        """Per sender, the run of pooled txs that starts at its next nonce
        (state's, or the overlay's) with no gap; senders with none left out."""
        pooled: List[Tuple[bytes, List[_Entry]]] = []
        for shard in self._shards:
            with shard.lock:
                pooled.extend((s, c[:]) for s, c in shard.chains.items())
        chains: List[List[_Entry]] = []
        for sender, chain in pooled:
            if exclude:
                # claimed by an in-flight block
                chain = [e for e in chain if e[2] not in exclude]
                if not chain:
                    continue
            if nonce_override is not None and sender in nonce_override:
                nonce = nonce_override[sender]
            else:
                nonce = self._account_nonce(sender)
            # the chain's FIRST tx has to be the next nonce: a stale one
            # ahead of it (not yet sanitized) strands the sender, and a gap
            # makes every later nonce unexecutable
            k = 0
            while k < len(chain) and chain[k][0] == nonce + k:
                k += 1
            del chain[k:]  # the copy is ours
            if chain:
                chains.append(chain)
        return chains

    # -- lifecycle --------------------------------------------------------------
    def remove_included(self, tx_hashes) -> None:
        tx_hashes = list(tx_hashes)
        with tracing.span("pool.remove_included", "pool", n=len(tx_hashes)) as sid:
            keys = [self._evict(h) for h in tx_hashes]
            tracing.annotate(sid, writes=self._delete_rows(keys))

    def sanitize(self) -> int:
        """Drop txs whose nonce is now stale (reference sanitize-on-persist,
        TransactionPool.cs:79-90). Returns number evicted."""
        keys: List[bytes] = []
        with tracing.span("pool.sanitize", "pool") as sid:
            for shard in self._shards:
                with shard.lock:
                    for sender, chain in list(shard.chains.items()):
                        stale = bisect_left(chain, (self._account_nonce(sender),))
                        for entry in chain[:stale]:
                            keys.append(self._forget(shard, entry[2]))
                        del chain[:stale]
                        if not chain:
                            del shard.chains[sender]
            tracing.annotate(
                sid, evicted=len(keys), writes=self._delete_rows(keys)
            )
        return len(keys)

    def _delete_rows(self, keys: List[bytes]) -> int:
        """The repository half of an eviction: every key of the call in ONE
        atomic write, issued with no shard lock held, so admission into a
        shard never queues behind the store's fsync. Returns the writes
        issued (0 or 1).

        Memory has forgotten these transactions already, as it did per hash
        when each had its own delete. A crash before the write leaves their
        rows behind, all or none, and `restore()` drops each on re-admission
        (`add` refuses a nonce below the account's). A concurrent `add` of
        one of them cannot put back a row that this write then deletes from
        under a pooled transaction: `remove_included` runs after the block's
        commit and `sanitize` picks what is stale, so each has a nonce below
        its account's and `add` refuses it before its `put`."""
        if not keys:
            return 0
        self._kv.write_batch([], keys)
        metrics.inc("txpool_evict_writes_total")
        return 1

    def restore(self) -> int:
        """Reload persisted pool txs (reference Restore, TransactionPool.cs:98)."""
        count = 0
        for key, enc in self._kv.scan_prefix(prefixed(EntryPrefix.POOL_TX)):
            try:
                stx = SignedTransaction.decode(enc)
            except (ValueError, AssertionError):
                self._kv.delete(key)
                continue
            if self.add(stx):
                count += 1
            else:
                # rejected on re-admission (stale nonce, fee floor, ...):
                # drop the persisted entry or it is re-read every restart
                self._kv.delete(key)
        return count

    def _evict(self, h: bytes) -> bytes:
        """Forgets `h` if pooled; either way returns its repository key: a
        hash not pooled (or evicted since the lookup) may still have a row."""
        sender = self._sender_of.get(h)
        if sender is not None:
            shard = self._shard(sender)
            with shard.lock:
                stx = shard.txs.get(h)
                if stx is not None:
                    key = self._forget(shard, h)
                    chain = shard.chains[sender]
                    del chain[bisect_left(chain, (stx.tx.nonce,))]
                    if not chain:
                        del shard.chains[sender]
                    return key
        return prefixed(EntryPrefix.POOL_TX, h)

    def _forget(self, shard: _PoolShard, h: bytes) -> bytes:
        """Caller holds shard.lock and `h` is pooled there. Drops everything
        of `h` in memory but its chain entry, which the caller replaces or
        deletes, and returns its repository key, which the caller deletes."""
        del shard.txs[h]
        del self._sender_of[h]
        return prefixed(EntryPrefix.POOL_TX, h)

    def tx_hashes(self) -> set:
        """Snapshot of pooled tx hashes (pending-tx filters)."""
        out = set()
        for shard in self._shards:
            with shard.lock:
                out.update(shard.txs)
        return out

    def clear(self) -> None:
        """Drop every pooled tx, memory AND persisted entries (reference
        clearInMemoryPool + repository delete, TransactionPool.cs)."""
        keys: List[bytes] = []
        for shard in self._shards:
            with shard.lock:
                keys.extend(self._forget(shard, h) for h in list(shard.txs))
                shard.chains.clear()
        # one write for the call, outside the locks like an eviction's. A tx
        # cleared here and admitted again before the write loses its row, not
        # its place in the pool: the repository is best-effort (see `add`)
        if keys:
            self._kv.write_batch([], keys)

    def persisted_hashes(self) -> List[bytes]:
        """Hashes of txs currently saved in the crash-restore repository."""
        plen = len(prefixed(EntryPrefix.POOL_TX))
        return [
            key[plen:]
            for key, _ in self._kv.scan_prefix(prefixed(EntryPrefix.POOL_TX))
        ]

    def clear_persisted(self) -> int:
        """Wipe the crash-restore repository WITHOUT touching the live pool
        (reference deleteTransactionPoolRepository)."""
        n = 0
        for key, _ in list(
            self._kv.scan_prefix(prefixed(EntryPrefix.POOL_TX))
        ):
            self._kv.delete(key)
            n += 1
        return n

    def get(self, h: bytes) -> Optional[SignedTransaction]:
        sender = self._sender_of.get(h)
        if sender is None:
            return None
        return self._shard(sender).txs.get(h)


def _merge(
    chains: List[List[_Entry]], limit: int
) -> List[Tuple[int, _Entry]]:
    """The first `limit` entries of the chains' fee-ordered merge, each with
    its chain's index: repeatedly the best key among the next-executable
    txs, so a cheap prerequisite nonce never strands an expensive later one
    (chain heads advance as they are picked). Keys are unique, so the heap
    compares nothing past them."""
    picked: List[Tuple[int, _Entry]] = []
    heap = [(chain[0][1], j, 0) for j, chain in enumerate(chains)]
    heapq.heapify(heap)
    while len(picked) < limit and heap:
        _, j, i = heapq.heappop(heap)
        chain = chains[j]
        picked.append((j, chain[i]))
        if i + 1 < len(chain):
            heapq.heappush(heap, (chain[i + 1][1], j, i + 1))
    return picked
