"""Which path a block takes, and that the path never shows (PR 39).

`execution.lanes` and `execution.merkleWorkers` choose between a serial
and a threaded route through `BlockManager.emulate` and `Trie.apply_many`.
Held here, over the benchmark's two block shapes (perfbench/traffic/
full.json: 700 transfers from 256 senders to 250 recipients; smallbank-
full.json: 700 calls of one contract) and a block of 24 disjoint groups:
every setting gives the same receipts, roots and pending node set; a
setting of 0 sends neither benchmark shape to a thread pool while a
forced N > 1 still does; and a freeze that went through shard workers
leaves its nodes in the caller's cache as a serial one does.
"""
import itertools
import json
import os
import random

import pytest

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
from lachain_tpu.storage.trie import EMPTY_ROOT, MIN_SHARD_OPS, Trie
from lachain_tpu.utils import metrics
from perfbench import traffic, traffic_smallbank

pytestmark = [pytest.mark.exec, pytest.mark.trie]

CHAIN = 225
SEED = 39
BLOCK = 700
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SETTINGS = [(0, 0), (1, 1), (8, 8)]  # (exec_lanes, merkle_workers)
SHAPES = ["transfers", "smallbank", "groups24"]
LANE_BLOCKS = "exec_blocks_parallel_total"
SHARDED = "trie_sharded_applies_total"


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


def _emulate_two_blocks(shape, lanes, workers):
    """Both blocks through BlockManager.emulate under one setting, the first
    committed before the second. -> per block (receipts, roots, node keys)."""
    addrs, genesis, blocks = _blocks(shape)
    state = StateManager(MemoryKV())
    state.trie.merkle_workers = workers
    executer = system_contracts.make_executer(CHAIN)
    snap = state.new_snapshot()
    for a in addrs:
        execution.set_balance(snap, a, 10**24)
    for i, stx in enumerate(genesis):
        assert executer.execute(snap, stx, 0, i).ok
    state.commit(0, snap.freeze())
    bm = BlockManager(state._kv, state, executer, lanes=lanes)
    out = []
    for height, txs in enumerate(blocks, start=1):
        ordered = BlockManager.order_transactions(txs, CHAIN)
        bm_mod._EMULATE_MEMO.clear()  # every setting shares one purity key
        em = bm.emulate(ordered, height)
        assert all(r.status == 1 for r in em.receipts), shape
        nodes = dict(state.trie.peek_pending())
        out.append(([r.encode() for r in em.receipts], em.roots, nodes))
        state.commit(height, em.roots)
    return out


_ORACLE = {}


def _oracle(shape):
    if shape not in _ORACLE:
        _ORACLE[shape] = _emulate_two_blocks(shape, 1, 1)
    return _ORACLE[shape]


def _counters():
    return [metrics.counter_value(n) or 0 for n in (LANE_BLOCKS, SHARDED)]


@pytest.mark.parametrize("lanes,workers", SETTINGS)
@pytest.mark.parametrize("shape", SHAPES)
def test_every_setting_gives_the_same_bytes(shape, lanes, workers):
    got = _emulate_two_blocks(shape, lanes, workers)
    for height, (mine, want) in enumerate(zip(got, _oracle(shape)), start=1):
        assert mine[0] == want[0], (shape, height, "receipts")
        assert mine[1] == want[1], (shape, height, "roots")
        assert mine[2] == want[2], (shape, height, "pending node set")


@pytest.mark.parametrize("shape", ["transfers", "smallbank"])
def test_zero_sends_a_benchmark_block_to_no_thread_pool(shape):
    before = _counters()
    _emulate_two_blocks(shape, 0, 0)
    assert _counters() == before


@pytest.mark.parametrize("shape", ["transfers", "smallbank"])
def test_a_forced_count_still_takes_lanes_and_shard_workers(shape):
    before = _counters()
    _emulate_two_blocks(shape, 8, 8)
    lane_blocks, sharded = (a - b for a, b in zip(_counters(), before))
    assert lane_blocks == 2
    # block 2 at least: balances and receipts over a root that is not empty
    assert sharded >= 2


class CountingKV(MemoryKV):
    def __init__(self):
        super().__init__()
        self.read = []

    def get(self, key):
        self.read.append(key)
        return super().get(key)


@pytest.mark.parametrize("workers", [1, 8])
def test_the_next_freeze_reads_back_no_node_the_last_one_wrote(workers):
    """Commit a freeze, then freeze again over the same keys: whichever path
    the first took, what it wrote is in the handle's cache, so the second
    asks the store for none of it."""
    rng = random.Random(workers)
    kv = CountingKV()
    trie = Trie(kv)
    keys = [rng.randbytes(20) for _ in range(3 * MIN_SHARD_OPS)]

    def commit():
        items = trie.peek_pending()
        kv.write_batch(items)
        trie.confirm_pending(items)
        return {k for k, _ in items}

    root = trie.apply_many(EMPTY_ROOT, {k: b"0" for k in keys}, workers=1)
    commit()
    sharded_before = metrics.counter_value(SHARDED) or 0
    root = trie.apply_many(root, {k: b"1" for k in keys[: 2 * MIN_SHARD_OPS]}, workers=workers)
    assert (metrics.counter_value(SHARDED) or 0) - sharded_before == (workers > 1)
    wrote = commit()
    assert len(wrote) > 2 * MIN_SHARD_OPS
    kv.read.clear()
    trie.apply_many(root, {k: b"2" for k in keys[: 2 * MIN_SHARD_OPS]}, workers=workers)
    assert not wrote.intersection(kv.read)
