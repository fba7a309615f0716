"""The indexed transaction pool against the scan it replaced.

`_ScanPool` is the pool as it was before the index: every call regroups,
sorts and reads the whole pool. It is kept here as the plain reference; the
differential test drives it and `TransactionPool` with the same seeded
sequences and asks for identical lists in identical order, identical return
values and an identical crash-restore repository after every step. Block
contents, and so block hashes, follow from `peek`'s order.

Below it: the nonce-read counter is bounded by senders, `restore()`, the
nonce memo against a commit that no pool call announces, and where the
pool's spans sit in a traced N=4 devnet era. Counts and order only: a CPU
run says nothing about time.
"""
import heapq
import random

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
    accounts = []
    for i in range(5):
        priv = ecdsa.generate_private_key(Rng(4000 + i))
        accounts.append((priv, ecdsa.address_from_public_key(ecdsa.public_key_bytes(priv))))
    state = {accounts[0][1]: 2}
    kv = MemoryKV()
    pool = TransactionPool(kv, CHAIN, lambda a: state.get(a, 0))

    def signed(k, nonce, gas_price, value=1):
        tx = Transaction(
            to=b"\x09" * 20, value=value, nonce=nonce, gas_price=gas_price, gas_limit=21000
        )
        return sign_transaction(tx, accounts[k][0], CHAIN)

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
    accounts = []
    for i in range(6):
        priv = ecdsa.generate_private_key(Rng(5000 + i))
        accounts.append((priv, ecdsa.address_from_public_key(ecdsa.public_key_bytes(priv))))
    net = Devnet(
        4, 1, seed=9, txs_per_block=100, engine="native", rbc_batch=True,
        initial_balances={addr: 10**18 for _p, addr in accounts},
    )
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
    submits = by_name["devnet.submit_tx"]
    assert len(submits) == sent
    assert not any(_inside(s, era) for s in submits for era in eras)
