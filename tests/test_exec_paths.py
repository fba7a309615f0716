"""Which hashing route a block's freeze takes, and that the route never
shows.

`Trie.apply_many` picks from its input: each node hashed as it is stored
below MIN_DEFER_OPS ops, deferred level-batched hashing from there on, and
native hashing threads only for a level of MIN_HASH_THREAD_BYTES. Held
here, over the benchmark's two block shapes (perfbench/traffic/full.json:
700 transfers from 256 senders to 250 recipients; smallbank-full.json: 700
calls of one contract) and a block of 24 disjoint groups: every route gives
the same receipts, roots and pending node set as the immediate walk; a
block of hb64.full's size hashes on one thread; and a freeze leaves what it
wrote in the handle's cache on either side of the byte floor.
"""
import itertools
import json
import os
import random

import pytest

import lachain_tpu.storage.trie as trie_mod
from lachain_tpu.core import block_manager as bm_mod
from lachain_tpu.core import execution, system_contracts
from lachain_tpu.core.block_manager import BlockManager
from lachain_tpu.core.types import (
    SignedTransaction,
    Transaction,
    sign_transaction,
    warm_sender_caches,
)
from lachain_tpu.crypto import ecdsa
from lachain_tpu.storage.kv import MemoryKV
from lachain_tpu.storage.state import StateManager
from lachain_tpu.storage.trie import EMPTY_ROOT, Trie, _host_cores
from perfbench import traffic, traffic_smallbank

pytestmark = [pytest.mark.exec, pytest.mark.trie]

CHAIN = 225
SEED = 39
BLOCK = 700
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPES = ["transfers", "smallbank", "groups24"]
# route -> (MIN_DEFER_OPS, MIN_HASH_THREAD_BYTES): the immediate walk with
# the defer floor raised past any block, deferred hashing at the program's
# floors, and deferred hashing with every level sent to the threads
ROUTES = {
    "immediate": (1 << 30, trie_mod.MIN_HASH_THREAD_BYTES),
    "deferred": (trie_mod.MIN_DEFER_OPS, trie_mod.MIN_HASH_THREAD_BYTES),
    "threaded": (trie_mod.MIN_DEFER_OPS, 0),
}


def _mix(name):
    with open(os.path.join(REPO, "perfbench", "traffic", name + ".json")) as fh:
        return json.load(fh)


_BLOCKS = {}


def _blocks(shape):
    """(funded addresses, genesis transactions, [block 1, block 2]) of a
    shape, signed once a process; the stream of `smallbank` opens with the
    contract's deployment, which genesis executes."""
    if shape in _BLOCKS:
        return _BLOCKS[shape]
    keys = traffic.account_keys(SEED, 256)
    addrs = [ecdsa.address_from_public_key(ecdsa.public_key_bytes(k)) for k in keys]
    first = 0
    if shape == "transfers":
        stream = traffic.signed_stream(_mix("full"), SEED, CHAIN)
        count = BLOCK
    elif shape == "smallbank":
        stream = traffic_smallbank.signed_stream(_mix("smallbank-full"), SEED, CHAIN)
        count, first = BLOCK, 1
    else:
        # 24 senders, a recipient each: 24 footprint groups of 4 transfers
        def pairs():
            for k in itertools.count():
                yield sign_transaction(
                    Transaction(
                        to=traffic.recipient(SEED, k % 24), value=1, nonce=k // 24,
                        gas_price=1, gas_limit=21000,
                    ),
                    keys[k % 24], CHAIN,
                ).encode()

        stream, count = pairs(), 96
    raw = list(itertools.islice(stream, first + 2 * count))
    stxs = [SignedTransaction.decode(r) for r in raw]
    warm_sender_caches(stxs, CHAIN)
    calls = stxs[first:]
    _BLOCKS[shape] = addrs, stxs[:first], [calls[:count], calls[count:]]
    return _BLOCKS[shape]


def _route(monkeypatch, route):
    """Set the trie's floors for `route`; -> the list that records, per
    native keccak batch, (threads asked for, bytes hashed)."""
    defer_from, thread_from = ROUTES[route]
    monkeypatch.setattr(trie_mod, "MIN_DEFER_OPS", defer_from)
    monkeypatch.setattr(trie_mod, "MIN_HASH_THREAD_BYTES", thread_from)
    calls = []
    real = trie_mod.keccak256_batch

    def spy(encs, threads):
        calls.append((threads, sum(map(len, encs))))
        return real(encs, threads)

    monkeypatch.setattr(trie_mod, "keccak256_batch", spy)
    return calls


def _emulate(shape, blocks):
    """`blocks` through BlockManager.emulate, each committed before the
    next. -> per block (receipts, roots, node keys)."""
    addrs, genesis, _ = _blocks(shape)
    state = StateManager(MemoryKV())
    executer = system_contracts.make_executer(CHAIN)
    snap = state.new_snapshot()
    for a in addrs:
        execution.set_balance(snap, a, 10**24)
    for i, stx in enumerate(genesis):
        assert executer.execute(snap, stx, 0, i).ok
    state.commit(0, snap.freeze())
    bm = BlockManager(state._kv, state, executer)
    out = []
    for height, txs in enumerate(blocks, start=1):
        ordered = BlockManager.order_transactions(txs, CHAIN)
        bm_mod._EMULATE_MEMO.clear()  # every route shares one purity key
        em = bm.emulate(ordered, height)
        assert all(r.status == 1 for r in em.receipts), shape
        nodes = dict(state.trie.peek_pending())
        out.append(([r.encode() for r in em.receipts], em.roots, nodes))
        state.commit(height, em.roots)
    return out


_ORACLE = {}


def _oracle(shape):
    """Both blocks of a shape on the immediate walk."""
    if shape not in _ORACLE:
        with pytest.MonkeyPatch.context() as m:
            _route(m, "immediate")
            _ORACLE[shape] = _emulate(shape, _blocks(shape)[2])
    return _ORACLE[shape]


@pytest.mark.parametrize("route", list(ROUTES))
@pytest.mark.parametrize("shape", SHAPES)
def test_every_setting_gives_the_same_bytes(shape, route, monkeypatch):
    want = _oracle(shape)
    calls = _route(monkeypatch, route)
    got = _emulate(shape, _blocks(shape)[2])
    for height, (mine, oracle) in enumerate(zip(got, want), start=1):
        assert mine[0] == oracle[0], (shape, height, "receipts")
        assert mine[1] == oracle[1], (shape, height, "roots")
        assert mine[2] == oracle[2], (shape, height, "pending node set")
    # the route was the one asked for
    if route == "immediate":
        assert calls == []
    else:
        assert calls
        want_threads = _host_cores() if route == "threaded" else 1
        assert {threads for threads, _ in calls} == {want_threads}, route


def test_a_block_of_hb64_size_hashes_on_one_thread(monkeypatch):
    """hb64.full's blocks hold up to 1000 transfers (perfbench/configs/
    hb64-sim.json txs_per_block): no level of their freeze comes near
    MIN_HASH_THREAD_BYTES, so the program's byte rule hashes each on the
    calling thread."""
    _, _, (first, second) = _blocks("transfers")
    block = first + second[: 1000 - len(first)]
    assert len(block) == 1000
    calls = _route(monkeypatch, "deferred")
    _emulate("transfers", [block])
    assert calls and {threads for threads, _ in calls} == {1}
    largest = max(size for _, size in calls)
    assert largest < trie_mod.MIN_HASH_THREAD_BYTES // 2, largest


class CountingKV(MemoryKV):
    def __init__(self):
        super().__init__()
        self.read = []

    def get(self, key):
        self.read.append(key)
        return super().get(key)


@pytest.mark.parametrize("route", ["deferred", "threaded"])
def test_the_next_freeze_reads_back_no_node_the_last_one_wrote(route, monkeypatch):
    """Commit a freeze, then freeze again over the same keys: whichever
    side of the byte floor the first hashed on, what it wrote is in the
    handle's cache, so the second asks the store for none of it."""
    calls = _route(monkeypatch, route)
    rng = random.Random(route)
    kv = CountingKV()
    trie = Trie(kv)
    keys = [rng.randbytes(20) for _ in range(1536)]

    def commit():
        items = trie.peek_pending()
        kv.write_batch(items)
        trie.confirm_pending(items)
        return {k for k, _ in items}

    root = trie.apply_many(EMPTY_ROOT, {k: b"0" for k in keys})
    commit()
    root = trie.apply_many(root, {k: b"1" for k in keys[:1024]})
    wrote = commit()
    assert len(wrote) > 1024
    kv.read.clear()
    trie.apply_many(root, {k: b"2" for k in keys[:1024]})
    assert not wrote.intersection(kv.read)
    want_threads = _host_cores() if route == "threaded" else 1
    assert {threads for threads, _ in calls} == {want_threads}
