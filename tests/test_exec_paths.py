"""Which route a block's freeze takes, and that the route never shows.

A validator's state walks in native code: `Trie.apply_many` is one native
call a tree (`native`). Any other state walks in Python, each node hashed
as it is stored (`immediate`). Held here, over the benchmark's two block
shapes (perfbench/traffic/full.json: 700 transfers from 256 senders to 250
recipients; smallbank-full.json: 700 calls of one contract) and a block of
24 disjoint groups: the native route gives the same receipts, roots and
pending node set as the immediate walk, and a freeze leaves what it wrote
in the handle's cache (the native table, on the native route).
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
from lachain_tpu.storage.trie import EMPTY_ROOT, Trie
from lachain_tpu.utils import metrics
from perfbench import traffic, traffic_smallbank

pytestmark = [pytest.mark.exec, pytest.mark.trie]

CHAIN = 225
SEED = 39
BLOCK = 700
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPES = ["transfers", "smallbank", "groups24"]
ROUTES = ["immediate", "native"]


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


def _native(route):
    """Whether `route` walks in native code; the library must be there."""
    if route == "native":
        assert trie_mod.native_walks() is not None
    return route == "native"


def _emulate(shape, blocks, route):
    """`blocks` through BlockManager.emulate on `route`, each committed
    before the next. -> per block (receipts, roots, node keys)."""
    addrs, genesis, _ = _blocks(shape)
    state = StateManager(MemoryKV(), native=_native(route))
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
        _ORACLE[shape] = _emulate(shape, _blocks(shape)[2], "immediate")
    return _ORACLE[shape]


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("shape", SHAPES)
def test_every_setting_gives_the_same_bytes(shape, route):
    want = _oracle(shape)
    hashed = metrics.counter_value("trie_nodes_hashed_total") or 0
    got = _emulate(shape, _blocks(shape)[2], route)
    hashed = (metrics.counter_value("trie_nodes_hashed_total") or 0) - hashed
    for height, (mine, oracle) in enumerate(zip(got, want), start=1):
        assert mine[0] == oracle[0], (shape, height, "receipts")
        assert mine[1] == oracle[1], (shape, height, "roots")
        assert mine[2] == oracle[2], (shape, height, "pending node set")
    # the route was the one asked for: only the native walk counts the
    # nodes it hashed
    assert (hashed > 0) == (route == "native"), (route, hashed)


class CountingKV(MemoryKV):
    def __init__(self):
        super().__init__()
        self.read = []

    def get(self, key):
        self.read.append(key)
        return super().get(key)


@pytest.mark.parametrize("route", ROUTES)
def test_the_next_freeze_reads_back_no_node_the_last_one_wrote(route):
    """Commit a freeze, then freeze again over the same keys: what the
    first wrote is in the handle's cache (the native table on the native
    route), so the second asks the store for none of it."""
    rng = random.Random(route)
    kv = CountingKV()
    trie = Trie(kv, native=_native(route))
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
    assert (trie._table is not None) == (route == "native")
