"""The indexed transaction pool against the scan it replaced.

`_ScanPool` is the pool as it was before the index: every call regroups,
sorts and reads the whole pool. It is kept here as the plain reference; the
differential test drives it and `TransactionPool` with the same seeded
sequences and asks for identical lists in identical order, identical return
values and an identical crash-restore repository after every step. Block
contents, and so block hashes, follow from `peek`'s order.

Below it: the nonce-read counter is bounded by senders, `restore()`, what an
eviction writes to the repository (one batch a call, its crash window, its
race with admission), the nonce memo against a commit that no pool call
announces, and where the pool's spans sit in a traced N=4 devnet era. Counts
and order only: a CPU run says nothing about time.
"""
import heapq
import random
import sys
import threading
import time

import pytest

from lachain_tpu.core.devnet import Devnet
from lachain_tpu.core.execution import get_nonce, set_nonce
from lachain_tpu.core.tx_pool import StateNonces, TransactionPool
from lachain_tpu.core.types import (
    SignedTransaction,
    Transaction,
    sign_transaction,
)
from lachain_tpu.crypto import ecdsa
from lachain_tpu.storage.kv import EntryPrefix, MemoryKV, prefixed
from lachain_tpu.storage.state import StateManager
from lachain_tpu.utils import metrics, tracing

CHAIN = 41
READS = "txpool_state_nonce_reads_total"
EVICT_WRITES = "txpool_evict_writes_total"


class Rng:
    def __init__(self, seed):
        self._r = random.Random(seed)

    def randbelow(self, n):
        return self._r.randrange(n)


def _stx(sender: bytes, nonce: int, gas_price: int, salt: int = 0):
    """A pooled-shape tx whose sender is given, not recovered: the pool
    asks `sender()` once and never looks at the signature."""
    tx = Transaction(
        to=b"\x07" * 20, value=salt, nonce=nonce, gas_price=gas_price, gas_limit=21000
    )
    stx = SignedTransaction(tx, sender + bytes(45))
    object.__setattr__(stx, "_sender_cache", (CHAIN, sender))
    return stx


def _keys(n: int, seed: int):
    """(private key, address) of `n` accounts whose transactions carry real
    signatures: what restore() needs, which recovers every sender again."""
    keys = []
    for i in range(n):
        priv = ecdsa.generate_private_key(Rng(seed + i))
        keys.append((priv, ecdsa.address_from_public_key(ecdsa.public_key_bytes(priv))))
    return keys


def _signed(priv, nonce, gas_price, value=1):
    tx = Transaction(
        to=b"\x09" * 20, value=value, nonce=nonce, gas_price=gas_price, gas_limit=21000
    )
    return sign_transaction(tx, priv, CHAIN)


# ---------------------------------------------------------------------------
# the reference: the pool before the index, one lock domain, no shards
# ---------------------------------------------------------------------------


class _ScanPool:
    def __init__(self, kv, account_nonce, min_gas_price=1):
        self._kv = kv
        self._account_nonce = account_nonce
        self.min_gas_price = min_gas_price
        self.txs = {}
        self.senders = {}
        self.by_nonce = {}

    def __len__(self):
        return len(self.txs)

    def add(self, stx):
        h = stx.hash()
        if stx.tx.gas_price < self.min_gas_price:
            return False
        if h in self.txs:
            return False
        sender = stx.sender(CHAIN)
        if sender is None:
            return False
        if stx.tx.nonce < self._account_nonce(sender):
            return False
        key = (sender, stx.tx.nonce)
        if key in self.by_nonce:
            old = self.txs.get(self.by_nonce[key])
            if old is not None and stx.tx.gas_price <= old.tx.gas_price:
                return False
            self._evict(self.by_nonce[key])
        self.txs[h] = stx
        self.senders[h] = sender
        self.by_nonce[key] = h
        self._kv.put(prefixed(EntryPrefix.POOL_TX, h), stx.encode())
        return True

    def next_nonce(self, sender):
        nonce = self._account_nonce(sender)
        while (sender, nonce) in self.by_nonce:
            nonce += 1
        return nonce

    def peek(self, max_txs, rng=None, window_txs=None, exclude=None, nonce_override=None):
        if rng is not None:
            window = self._ordered(
                window_txs if window_txs is not None else 4 * max_txs,
                exclude,
                nonce_override,
            )
            if len(window) > max_txs:
                by_sender, order = {}, []
                for s, stx in window:
                    if s not in by_sender:
                        by_sender[s] = []
                        order.append(s)
                    by_sender[s].append(stx)
                rng.shuffle(order)
                picked = []
                for s in order:
                    take = min(len(by_sender[s]), max_txs - len(picked))
                    picked.extend(by_sender[s][:take])
                    if len(picked) >= max_txs:
                        break
                return picked
            return [stx for _, stx in window]
        return [stx for _, stx in self._ordered(max_txs, exclude, nonce_override)]

    def _ordered(self, max_txs, exclude, nonce_override):
        per_sender = {}
        for h, stx in self.txs.items():
            if exclude is not None and h in exclude:
                continue
            per_sender.setdefault(self.senders[h], []).append(stx)
        chains = {}
        for sender, txs in per_sender.items():
            txs.sort(key=lambda t: t.tx.nonce)
            if nonce_override is not None and sender in nonce_override:
                nonce = nonce_override[sender]
            else:
                nonce = self._account_nonce(sender)
            chain = []
            for t in txs:
                if t.tx.nonce != nonce:
                    break
                chain.append(t)
                nonce += 1
            if chain:
                chains[sender] = chain

        def heap_key(stx):
            return (-stx.tx.gas_price, bytes(255 - b for b in stx.hash()))

        picked = []
        heap = [(heap_key(chain[0]), s, 0) for s, chain in chains.items()]
        heapq.heapify(heap)
        while len(picked) < max_txs and heap:
            _, s, i = heapq.heappop(heap)
            picked.append((s, chains[s][i]))
            if i + 1 < len(chains[s]):
                heapq.heappush(heap, (heap_key(chains[s][i + 1]), s, i + 1))
        return picked

    def remove_included(self, tx_hashes):
        for h in tx_hashes:
            self._evict(h)

    def sanitize(self):
        stale = [
            h
            for h, stx in self.txs.items()
            if stx.tx.nonce < self._account_nonce(self.senders[h])
        ]
        for h in stale:
            self._evict(h)
        return len(stale)

    def _evict(self, h):
        stx = self.txs.pop(h, None)
        sender = self.senders.pop(h, None)
        if stx is not None and sender is not None:
            self.by_nonce.pop((sender, stx.tx.nonce), None)
        self._kv.delete(prefixed(EntryPrefix.POOL_TX, h))


def _persisted(kv):
    plen = len(prefixed(EntryPrefix.POOL_TX))
    return sorted(k[plen:] for k, _ in kv.scan_prefix(prefixed(EntryPrefix.POOL_TX)))


class _Pair:
    """The indexed pool and the scan over one account state, kept in step."""

    def __init__(self, rnd: random.Random, n_senders: int):
        self.rnd = rnd
        # first bytes spread over and collide within the 16 shards
        self.senders = [rnd.randbytes(20) for _ in range(n_senders)]
        self.state = {}
        self.kv_new, self.kv_ref = MemoryKV(), MemoryKV()
        read = lambda a: self.state.get(a, 0)  # noqa: E731
        self.new = TransactionPool(self.kv_new, CHAIN, read)
        self.ref = _ScanPool(self.kv_ref, read)
        self.made = []  # every tx ever offered, admitted or not
        self.steps = 0

    def both(self, call):
        got, want = call(self.new), call(self.ref)
        assert got == want, (self.steps, got, want)
        return want

    def check(self):
        self.steps += 1
        held = sorted(self.ref.txs)
        assert len(self.new) == len(held)
        assert sorted(self.new.tx_hashes()) == held
        assert sorted(self.new.persisted_hashes()) == held
        assert _persisted(self.kv_ref) == held
        probe = self.rnd.choice(self.made) if self.made else None
        if probe is not None:
            assert self.new.get(probe.hash()) is self.ref.txs.get(probe.hash())
            assert self.new.precheck(probe) == (
                probe.tx.gas_price >= 1 and probe.hash() not in self.ref.txs
            )

    # -- steps ----------------------------------------------------------------
    def offer(self, stx):
        self.made.append(stx)
        return self.both(lambda p: p.add(stx))

    def add_some(self):
        rnd = self.rnd
        sender = rnd.choice(self.senders)
        base = self.ref.next_nonce(sender)
        kind = rnd.random()
        if kind < 0.55:
            nonce = base  # extends the chain
        elif kind < 0.75:
            nonce = base + rnd.randint(1, 3)  # leaves a gap
        elif kind < 0.9:
            # an occupied or stale nonce: replace-by-fee or refusal
            nonce = max(0, base - rnd.randint(1, 4))
        else:
            nonce = rnd.randint(0, 12)
        # gas price 0 is under the floor
        self.offer(_stx(sender, nonce, rnd.randint(0, 7), salt=len(self.made)))

    def add_duplicate(self):
        if self.made:
            self.offer(self.rnd.choice(self.made))

    def remove_some(self):
        held = list(self.ref.txs)
        hashes = self.rnd.sample(held, min(len(held), self.rnd.randint(0, 6)))
        hashes.append(self.rnd.randbytes(32))  # one the pool never held
        self.rnd.shuffle(hashes)
        self.both(lambda p: p.remove_included(list(hashes)))

    def commit_some(self):
        """A block lands: its senders' nonces advance, the block's txs are
        removed, the rest is sanitized — or, as the synchronizer does, not."""
        for sender in self.rnd.sample(self.senders, self.rnd.randint(1, 3)):
            self.state[sender] = self.state.get(sender, 0) + self.rnd.randint(1, 3)
        if self.rnd.random() < 0.7:
            self.both(lambda p: p.sanitize())

    def peek_some(self):
        rnd = self.rnd
        size = len(self.ref)
        max_txs = rnd.choice([0, 1, 3, 8, size // 2, size, size + 5])
        kw = {}
        if rnd.random() < 0.4:
            held = list(self.ref.txs)
            kw["exclude"] = set(rnd.sample(held, min(len(held), rnd.randint(0, 8))))
        if rnd.random() < 0.4:
            kw["nonce_override"] = {
                s: self.state.get(s, 0) + rnd.randint(0, 3)
                for s in rnd.sample(self.senders, rnd.randint(1, 3))
            }
            if rnd.random() < 0.5:
                # the overlay as the producer builds it: what the in-flight
                # blocks claimed is masked, and the chain starts past it
                kw["exclude"] = {
                    h
                    for h, stx in self.ref.txs.items()
                    if stx.tx.nonce < kw["nonce_override"].get(self.ref.senders[h], 0)
                }
        self.both(lambda p: [t.hash() for t in p.peek(max_txs, **kw)])
        seed = rnd.getrandbits(32)
        window = rnd.choice([None, 0, 2, max_txs, size // 2, size, 2 * size + 1])
        self.both(
            lambda p: [
                t.hash()
                for t in p.peek(
                    max_txs, rng=random.Random(seed), window_txs=window, **kw
                )
            ]
        )

    def next_nonces(self):
        for sender in self.rnd.sample(self.senders, 3) + [self.rnd.randbytes(20)]:
            self.both(lambda p: p.next_nonce(sender))


@pytest.mark.parametrize("seed", range(10))
def test_indexed_pool_matches_the_scan(seed):
    rnd = random.Random(1000 + seed)
    pair = _Pair(rnd, n_senders=rnd.choice([3, 12, 40]))
    steps = (
        [pair.add_some] * 12
        + [pair.add_duplicate, pair.remove_some, pair.commit_some]
        + [pair.peek_some] * 3
        + [pair.next_nonces]
    )
    for _ in range(400):
        rnd.choice(steps)()
        pair.check()
    assert len(pair.ref) > 0, "the sequence should leave something pooled"
    pair.both(lambda p: [t.hash() for t in p.peek(10**6)])
    pair.new.clear()
    assert len(pair.new) == 0 and pair.new.persisted_hashes() == []
    assert pair.new.peek(10) == [] and pair.new.sanitize() == 0


@pytest.mark.parametrize("seed", range(4))
def test_sampled_proposal_matches_the_scan_on_a_full_pool(seed):
    """The shape of every proposal at hb64.full: every chain executable,
    the window (two blocks' worth) holds them all, a proposal is a few."""
    rnd = random.Random(2000 + seed)
    pair = _Pair(rnd, n_senders=64)
    for sender in pair.senders:
        for nonce in range(rnd.randint(1, 12)):
            assert pair.offer(_stx(sender, nonce, rnd.randint(1, 7)))
    size = len(pair.ref)
    for height in range(20):
        for max_txs, window in ((4, 2 * size), (15, size), (15, size - 1), (15, 40)):
            pair.both(
                lambda p: [
                    t.hash()
                    for t in p.peek(
                        max_txs, rng=random.Random((seed << 20) ^ height), window_txs=window
                    )
                ]
            )


# ---------------------------------------------------------------------------
# what a call costs, in state reads
# ---------------------------------------------------------------------------


def _reads(call) -> int:
    before = metrics.counter_value(READS)
    call()
    return int(metrics.counter_value(READS) - before)


@pytest.mark.parametrize("per_sender", [2, 8, 32])
def test_nonce_reads_are_bounded_by_senders_not_by_pool_size(per_sender):
    senders = [random.Random(7).randbytes(19) + bytes([i]) for i in range(256)]
    state = {}
    pool = TransactionPool(MemoryKV(), CHAIN, lambda a: state.get(a, 0))
    adds = _reads(
        lambda: [
            pool.add(_stx(s, nonce, 1 + (nonce + s[-1]) % 7))
            for s in senders
            for nonce in range(per_sender)
        ]
    )
    assert len(pool) == 256 * per_sender and adds == len(pool)
    assert _reads(lambda: pool.peek(15, rng=random.Random(3), window_txs=10**6)) == 256
    assert _reads(lambda: pool.peek(10**6)) == 256
    assert _reads(pool.sanitize) == 256
    # a block of one tx a sender: still one read a sender, and the rest stays
    for s in senders:
        state[s] = 1
    evicted = []
    assert _reads(lambda: evicted.append(pool.sanitize())) == 256
    assert evicted == [256] and len(pool) == 256 * (per_sender - 1)
    included = [t.hash() for t in pool.peek(100)]
    assert _reads(lambda: pool.remove_included(included)) == 0
    assert len(pool) == 256 * (per_sender - 1) - 100


def test_two_pools_share_nothing():
    state = {}
    a = TransactionPool(MemoryKV(), CHAIN, lambda s: state.get(s, 0))
    b = TransactionPool(MemoryKV(), CHAIN, lambda s: state.get(s, 0))
    sender = b"\x05" * 20
    stx = _stx(sender, 0, 3)
    assert a.add(stx)
    assert len(b) == 0 and b.get(stx.hash()) is None and b.peek(5) == []
    assert b.precheck(stx) and b.next_nonce(sender) == 0
    assert b.add(stx) and a.next_nonce(sender) == b.next_nonce(sender) == 1
    a.remove_included([stx.hash()])
    assert len(a) == 0 and len(b) == 1


# ---------------------------------------------------------------------------
# restore
# ---------------------------------------------------------------------------


def test_restore_rebuilds_the_same_pool():
    """Real signatures: restore() decodes from the repository and recovers
    every sender again."""
    accounts = _keys(5, 4000)
    state = {accounts[0][1]: 2}
    kv = MemoryKV()
    pool = TransactionPool(kv, CHAIN, lambda a: state.get(a, 0))

    def signed(k, nonce, gas_price, value=1):
        return _signed(accounts[k][0], nonce, gas_price, value)

    offered = [
        signed(0, 2, 3), signed(0, 3, 1), signed(0, 5, 6),  # a gap at 4
        signed(1, 0, 2), signed(1, 0, 5, value=2),  # replaced by the richer
        signed(2, 0, 4), signed(2, 1, 4), signed(2, 2, 7),
        signed(3, 1, 2),  # not executable
        signed(0, 1, 9),  # stale: refused
    ]
    admitted = [pool.add(stx) for stx in offered]
    assert admitted == [True] * 4 + [True] * 5 + [False]
    assert len(pool) == 8
    # a persisted entry that no longer passes admission is dropped on restore
    stale = signed(4, 0, 1)
    assert pool.add(stale)
    state[accounts[4][1]] = 1

    again = TransactionPool(kv, CHAIN, lambda a: state.get(a, 0))
    assert again.restore() == 8
    assert again.tx_hashes() == pool.tx_hashes() - {stale.hash()}
    assert sorted(again.persisted_hashes()) == sorted(again.tx_hashes())
    pool.sanitize()
    assert [t.hash() for t in again.peek(100)] == [t.hash() for t in pool.peek(100)]
    for seed in range(5):
        assert [
            t.hash() for t in again.peek(3, rng=random.Random(seed), window_txs=100)
        ] == [t.hash() for t in pool.peek(3, rng=random.Random(seed), window_txs=100)]
    for _priv, addr in accounts:
        assert again.next_nonce(addr) == pool.next_nonce(addr)


# ---------------------------------------------------------------------------
# what an eviction writes to the repository
# ---------------------------------------------------------------------------


class _SpyKV(MemoryKV):
    """A MemoryKV that lists the writes it is asked for and can be told to
    fail the next batch, as a store whose process dies before it acts."""

    def __init__(self):
        super().__init__()
        self.calls = []
        self.fail_next_batch = False

    def put(self, key, value):
        self.calls.append(("put", key))
        super().put(key, value)

    def delete(self, key):
        self.calls.append(("delete", key))
        super().delete(key)

    def write_batch(self, puts, deletes=()):
        self.calls.append(("write_batch", [k for k, _ in puts], list(deletes)))
        if self.fail_next_batch:
            self.fail_next_batch = False
            raise IOError("the store is gone")
        super().write_batch(puts, deletes)


def _row(h: bytes) -> bytes:
    return prefixed(EntryPrefix.POOL_TX, h)


@pytest.mark.parametrize("k,strangers", [(1, 0), (5, 2), (300, 40), (0, 3)])
def test_an_eviction_is_one_write_whatever_it_evicts(k, strangers):
    """`k` pooled hashes and `strangers` the pool never held: one batch
    with a delete for each, no single delete, and the writes counted."""
    rnd = random.Random(k)
    kv = _SpyKV()
    pool = TransactionPool(kv, CHAIN, lambda a: 0)
    senders = [rnd.randbytes(20) for _ in range(24)]
    pooled = [_stx(senders[i % 24], i // 24, 1 + i % 5) for i in range(k + 30)]
    assert all(pool.add(stx) for stx in pooled)
    included = [stx.hash() for stx in rnd.sample(pooled, k)]
    included += [rnd.randbytes(32) for _ in range(strangers)]
    rnd.shuffle(included)
    kv.calls.clear()
    before = metrics.counter_value(EVICT_WRITES)
    pool.remove_included(iter(included))
    assert [c[0] for c in kv.calls] == ["write_batch"]
    assert kv.calls[0][1] == [] and kv.calls[0][2] == [_row(h) for h in included]
    assert metrics.counter_value(EVICT_WRITES) - before == 1
    assert len(pool) == 30 and not pool.tx_hashes() & set(included)
    assert sorted(pool.persisted_hashes()) == sorted(pool.tx_hashes())


def _three_deep_pool():
    """40 senders with nonces 0..2 pooled, over a state the test moves."""
    state = {}
    kv = _SpyKV()
    pool = TransactionPool(kv, CHAIN, lambda a: state.get(a, 0))
    senders = [bytes([i]) * 20 for i in range(40)]
    assert all(pool.add(_stx(s, nonce, 2)) for s in senders for nonce in range(3))
    kv.calls.clear()
    return state, kv, pool, senders


def test_an_eviction_with_nothing_to_evict_writes_nothing():
    _state, kv, pool, _senders = _three_deep_pool()
    before = metrics.counter_value(EVICT_WRITES)
    pool.remove_included([])
    assert pool.sanitize() == 0
    assert kv.calls == [] and metrics.counter_value(EVICT_WRITES) == before


def test_a_block_is_one_write_whoever_finds_its_transactions():
    state, kv, pool, senders = _three_deep_pool()
    before = metrics.counter_value(EVICT_WRITES)
    # the producer's order: a block of the first two nonces of every other
    # sender; remove_included writes once and leaves sanitize nothing
    block = [_stx(s, nonce, 2).hash() for s in senders[::2] for nonce in range(2)]
    for s in senders[::2]:
        state[s] = 2
    pool.remove_included(block)
    assert pool.sanitize() == 0
    assert kv.calls == [("write_batch", [], [_row(h) for h in block])]
    # the synchronizer's order: nonces move, nobody names the hashes; sanitize
    # finds them itself, over all shards, and writes once
    for s in senders[1::2]:
        state[s] = 1
    kv.calls.clear()
    assert pool.sanitize() == 20
    assert [c[0] for c in kv.calls] == ["write_batch"] and kv.calls[0][1] == []
    assert sorted(kv.calls[0][2]) == sorted(_row(_stx(s, 0, 2).hash()) for s in senders[1::2])
    assert metrics.counter_value(EVICT_WRITES) - before == 2
    assert sorted(pool.persisted_hashes()) == sorted(pool.tx_hashes()) and len(pool) == 60


def test_clear_is_one_write_and_leaves_a_pool_that_works():
    _state, kv, pool, senders = _three_deep_pool()
    before = metrics.counter_value(EVICT_WRITES)
    pool.clear()
    assert [c[0] for c in kv.calls] == ["write_batch"] and len(kv.calls[0][2]) == 120
    assert len(pool) == 0 and pool.persisted_hashes() == [] and pool.peek(10) == []
    assert pool.add(_stx(senders[0], 0, 2)) and pool.next_nonce(senders[0]) == 1
    kv.calls.clear()
    pool.clear()
    pool.clear()  # an empty pool: nothing to write
    assert [c[0] for c in kv.calls] == ["write_batch"]
    assert metrics.counter_value(EVICT_WRITES) == before, "the operator's verb is no eviction"


def test_a_fee_replacement_leaves_the_nonce_one_row():
    kv = _SpyKV()
    pool = TransactionPool(kv, CHAIN, lambda a: 0)
    sender = b"\x44" * 20
    cheap, rich = _stx(sender, 0, 2), _stx(sender, 0, 5, salt=1)
    assert pool.add(cheap) and pool.add(_stx(sender, 1, 1))
    kv.calls.clear()
    assert not pool.add(_stx(sender, 0, 2, salt=2)), "no richer: refused"
    assert kv.calls == []
    assert pool.add(rich)
    # the new row and the old row's delete are one atomic write
    assert kv.calls == [("write_batch", [_row(rich.hash())], [_row(cheap.hash())])]
    assert pool.get(cheap.hash()) is None and pool.get(rich.hash()) is rich
    assert sorted(pool.persisted_hashes()) == sorted(pool.tx_hashes())
    assert [t.hash() for t in pool.peek(10)][0] == rich.hash() and len(pool) == 2


@pytest.fixture(params=["memory", "lsm"])
def reopenable_kv(request, tmp_path):
    """open() gives the store; on the LSM engine each call after the first
    closes it and opens its directory again, as a restarted node does."""
    if request.param == "memory":
        kv = MemoryKV()
        yield lambda: kv
        return
    from lachain_tpu.storage.lsm import LsmKV

    held = []

    def open_():
        if held:
            held.pop().close()
        held.append(LsmKV(str(tmp_path / "db")))
        return held[-1]

    yield open_
    if held:
        held.pop().close()


def test_restore_after_evictions_rebuilds_the_same_pool(reopenable_kv):
    keys = _keys(4, 4100)
    state = {}
    read = lambda a: state.get(a, 0)  # noqa: E731
    pool = TransactionPool(reopenable_kv(), CHAIN, read)
    offered = [
        _signed(priv, nonce, 1 + (nonce + i) % 4)
        for i, (priv, _a) in enumerate(keys)
        for nonce in range(4)
    ]
    assert all(pool.add(stx) for stx in offered)
    # a block of every sender's nonce 0, and sender 3's nonce 1 besides;
    # sender 2's chain moved further than the block says (a synced block)
    block = [stx for stx in offered if stx.tx.nonce == 0] + [offered[13]]
    for _priv, addr in keys:
        state[addr] = 1
    state[keys[3][1]] = 2
    state[keys[2][1]] = 3
    pool.remove_included([stx.hash() for stx in block] + [b"\x5a" * 32])
    assert pool.sanitize() == 2
    assert len(pool) == 16 - 5 - 2
    assert sorted(pool.persisted_hashes()) == sorted(pool.tx_hashes())

    again = TransactionPool(reopenable_kv(), CHAIN, read)
    assert again.restore() == len(pool)
    assert again.tx_hashes() == pool.tx_hashes()
    assert sorted(again.persisted_hashes()) == sorted(again.tx_hashes())
    assert [t.hash() for t in again.peek(100)] == [t.hash() for t in pool.peek(100)]
    for _priv, addr in keys:
        assert again.next_nonce(addr) == pool.next_nonce(addr) == 4


def test_a_crash_between_forgetting_and_the_write_leaves_no_included_row():
    """Memory forgets first, the repository second. The store dies in
    between: every row of the block is still there, and the restart's
    restore() re-admits none of them and deletes each."""
    keys = _keys(3, 4200)
    state = {}
    read = lambda a: state.get(a, 0)  # noqa: E731
    kv = _SpyKV()
    pool = TransactionPool(kv, CHAIN, read)
    offered = [_signed(priv, nonce, 3) for priv, _a in keys for nonce in range(3)]
    assert all(pool.add(stx) for stx in offered)
    block = [stx for stx in offered if stx.tx.nonce < 2]
    for _priv, addr in keys:
        state[addr] = 2  # execute_block has committed
    kv.fail_next_batch = True
    with pytest.raises(IOError):
        pool.remove_included([stx.hash() for stx in block])
    left = {stx.hash() for stx in offered if stx.tx.nonce == 2}
    assert pool.tx_hashes() == left, "memory had forgotten the block already"
    assert set(pool.persisted_hashes()) == {stx.hash() for stx in offered}

    again = TransactionPool(kv, CHAIN, read)
    assert again.restore() == 3
    assert again.tx_hashes() == left and set(again.persisted_hashes()) == left
    assert all(again.get(stx.hash()) is None for stx in block)


def test_admission_races_eviction_and_the_repository_keeps_up():
    """More admitting threads than cores offer every tx over and over, the
    evicted ones too, while blocks commit and are evicted. A row deleted
    from under a pooled tx, or left behind an evicted one, shows at the end."""
    senders = [bytes([7 * i]) * 20 for i in range(32)]
    txs = {s: [_stx(s, nonce, 1 + nonce % 3) for nonce in range(12)] for s in senders}
    everything = [stx for chain in txs.values() for stx in chain]
    state = {}
    pool = TransactionPool(MemoryKV(), CHAIN, lambda a: state.get(a, 0))
    stop = threading.Event()
    failures = []

    def admit(seed):
        rnd = random.Random(seed)
        try:
            while not stop.is_set():
                pool.add(rnd.choice(everything))
        except Exception as e:  # noqa: BLE001 - asserted on below
            failures.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=admit, args=(i,)) for i in range(16)]
    try:
        for t in threads:
            t.start()
        rnd = random.Random(99)
        deadline = time.monotonic() + 20
        for height in range(1, 11):
            while len(pool) < 32 * (12 - height) and time.monotonic() < deadline:
                time.sleep(0.001)
            # a block: the next nonce of most senders; the rest move with no
            # hash named, and are sanitize's to find
            block = [txs[s][height - 1].hash() for s in senders if rnd.random() < 0.8]
            for s in senders:
                state[s] = height
            pool.remove_included(block)
            pool.sanitize()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not failures and not any(t.is_alive() for t in threads)
    want = {stx.hash() for stx in everything if stx.tx.nonce >= 10}
    for stx in everything:
        pool.add(stx)  # whatever the threads had not offered again yet
    assert pool.tx_hashes() == want
    assert set(pool.persisted_hashes()) == want


# ---------------------------------------------------------------------------
# the nonce memo: valid exactly as long as the committed state stands
# ---------------------------------------------------------------------------


def _commit_nonce(state: StateManager, height: int, addr: bytes, nonce: int) -> None:
    """What a block's execution leaves: a new committed root. No pool call."""
    snap = state.new_snapshot()
    set_nonce(snap, addr, nonce)
    state.commit(height, snap.freeze())


def test_memo_is_dropped_by_the_commit_itself():
    state = StateManager(MemoryKV())
    pool = TransactionPool(MemoryKV(), CHAIN, StateNonces(state))
    a, b = b"\x21" * 20, b"\x22" * 20
    assert _reads(lambda: pool.add(_stx(a, 0, 5))) == 1
    # nothing committed since: the next reads of `a` are the memo's
    assert _reads(lambda: pool.add(_stx(a, 1, 2))) == 0
    assert _reads(lambda: pool.next_nonce(a)) == 0 and pool.next_nonce(a) == 2
    assert _reads(lambda: pool.peek(10)) == 0
    assert _reads(lambda: pool.add(_stx(b, 0, 2))) == 1
    # the synchronizer's path: execute_block commits, then remove_included
    # alone — no sanitize ever tells the pool
    first = pool.peek(1)[0]
    _commit_nonce(state, 1, a, 1)
    pool.remove_included([first.hash()])
    assert not pool.add(_stx(a, 0, 9, salt=1)), "nonce 0 is used now"
    assert pool.next_nonce(a) == 2
    _commit_nonce(state, 2, a, 5)
    assert pool.next_nonce(a) == 5
    assert [t.tx.nonce for t in pool.peek(10)] == [0], "a's nonce 1 is stale, b's 0 stands"
    assert pool.sanitize() == 1 and len(pool) == 1
    # a rollback replaces the committed roots too
    state.rollback_to(1)
    assert pool.next_nonce(a) == 1
    assert pool.add(_stx(a, 1, 3, salt=2))


def test_a_plain_reader_is_never_memoised():
    """A callable with no `version` gives no way to see a commit: every
    call reads it."""
    state = {}
    sender = b"\x31" * 20
    pool = TransactionPool(MemoryKV(), CHAIN, lambda a: state.get(a, 0))
    assert pool.add(_stx(sender, 0, 1))
    state[sender] = 4
    assert _reads(lambda: pool.next_nonce(sender)) == 1 and pool.next_nonce(sender) == 4
    assert not pool.add(_stx(sender, 3, 1))


# ---------------------------------------------------------------------------
# the spans, in a traced N=4 devnet era
# ---------------------------------------------------------------------------


def _inside(s, outer) -> bool:
    return outer["start"] <= s["start"] and s["end"] <= outer["end"]


@pytest.mark.observability
def test_pool_spans_sit_where_the_metrics_expect_them():
    tracing.reset_for_tests()
    metrics.reset_all_for_tests()
    accounts = _keys(6, 5000)
    net = Devnet(
        4, 1, seed=9, txs_per_block=100, engine="native", rbc_batch=True,
        initial_balances={addr: 10**18 for _p, addr in accounts},
    )
    # when each pool's repository takes a batch of deletes, on the tracer's clock
    row_writes = []

    def timed(write_batch):
        def spy(puts, deletes=()):
            t0 = time.monotonic()
            write_batch(puts, deletes)
            if any(k.startswith(_row(b"")) for k in deletes):
                row_writes.append({"start": t0, "end": time.monotonic(), "n": len(deletes)})

        return spy

    for node in net.nodes:
        node.pool._kv.write_batch = timed(node.pool._kv.write_batch)
    try:
        sent = 0
        for era in (1, 2):
            for priv, _addr in accounts:
                for nonce in (2 * (era - 1), 2 * (era - 1) + 1):
                    tx = Transaction(
                        to=b"\x0a" * 20, value=1, nonce=nonce, gas_price=1 + nonce % 3,
                        gas_limit=100000,
                    )
                    assert net.submit_tx(sign_transaction(tx, priv, net.chain_id))
                    sent += 1
            net.run_era(era)
        # after a commit every pool answers from the new state
        for node in net.nodes:
            for _priv, addr in accounts:
                assert node.pool.next_nonce(addr) == get_nonce(node.state.new_snapshot(), addr) == 2 * era
    finally:
        net.close()
        spans = tracing.snapshot()
        tracing.reset_for_tests()
        evict_writes = metrics.counter_value(EVICT_WRITES)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert all(s["cat"] == "pool" and not s["open"] for n in (
        "pool.peek", "pool.remove_included", "pool.sanitize", "devnet.submit_tx"
    ) for s in by_name[n])
    eras = by_name["era"]
    assert len(eras) == 2
    for name, parent in (
        ("pool.peek", "cross.root_input"),
        ("pool.remove_included", "cross.root_produce"),
        ("pool.sanitize", "cross.root_produce"),
    ):
        assert len(by_name[name]) == 2 * 4, name  # once a validator an era
        for s in by_name[name]:
            assert sum(_inside(s, p) for p in by_name[parent]) == 1, (name, s)
    assert all("size" in s["args"] for s in by_name["pool.peek"])
    assert sum(s["args"]["n"] for s in by_name["pool.remove_included"]) == 4 * sent
    assert all(s["args"]["evicted"] == 0 for s in by_name["pool.sanitize"])
    # a block's eviction is one write, inside the span the metric reads
    assert all(s["args"]["writes"] == 1 for s in by_name["pool.remove_included"])
    assert all(s["args"]["writes"] == 0 for s in by_name["pool.sanitize"])
    assert evict_writes == len(row_writes) == 2 * 4
    for w in row_writes:
        inside = [s for s in by_name["pool.remove_included"] if _inside(w, s)]
        assert len(inside) == 1 and inside[0]["args"]["n"] == w["n"], w
    submits = by_name["devnet.submit_tx"]
    assert len(submits) == sent
    assert not any(_inside(s, era) for s in submits for era in eras)
