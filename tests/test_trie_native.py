"""The trie's native walks against the Python walker.

On a validator's state (`StateManager(kv, native=True)`, what Node and
devnet's make_node build) `Trie.apply_many` and `Trie.get` run in C++ over
a node table a Trie (storage/trie.py, crypto/native/trie.cpp). Held here:
a block the shape of hb128-share's (~21,000 balance and nonce writes,
~10,000 new receipts on a growing tree) gives the Python walker's roots,
pending node set and leaves, on MemoryKV and on an LsmKV reopened between
blocks so that every node comes through the loader; a read answers as the
Python walk does and a node the store lacks still raises KeyError; the
table's bound only costs loads, and a process's tables share one budget;
reads on one thread while another freezes; `trie_node_loads_total{source}`
says where the nodes came from; a library without the walks is an error;
and the benchmark's reference re-executes a validator's chain on the
Python walker.
"""
import ctypes
import gc
import random
import threading
from types import SimpleNamespace

import pytest

import lachain_tpu.storage.trie as trie_mod
from lachain_tpu.core import devnet
from lachain_tpu.core.block_manager import BlockManager
from lachain_tpu.core.types import Transaction, sign_transaction
from lachain_tpu.crypto import ecdsa
from lachain_tpu.crypto import hashes as hashes_mod
from lachain_tpu.storage.kv import EntryPrefix, MemoryKV, prefixed
from lachain_tpu.storage.lsm import LsmKV
from lachain_tpu.storage.state import StateManager
from lachain_tpu.storage.trie import EMPTY_ROOT, Trie
from lachain_tpu.utils import metrics
from perfbench import reference

pytestmark = [pytest.mark.trie, pytest.mark.storage]

BLOCKS = 10
SENDERS = 9906  # a balance and a nonce each, every block
RECIPIENTS = 1016  # a balance each
RECEIPTS = 10000  # new keys every block


@pytest.fixture(autouse=True)
def _library():
    assert trie_mod.native_walks() is not None


def _loads(source: str) -> float:
    return metrics.counter_value("trie_node_loads_total", {"source": source})


def _share_blocks():
    """Genesis writes and BLOCKS blocks of hb128-share's shape, as the
    executor leaves them in a snapshot: {tree: {key: value}}."""
    rng = random.Random(128)
    senders = [rng.randbytes(20) for _ in range(SENDERS)]
    recipients = [rng.randbytes(20) for _ in range(RECIPIENTS)]

    def balances():
        w = {}
        for a in senders:
            w[b"\x01" + a] = rng.randbytes(32)
            w[b"\x02" + a] = rng.randbytes(8)
        for a in recipients:
            w[b"\x01" + a] = rng.randbytes(32)
        return w

    genesis = {"balances": balances()}
    blocks = [
        {
            "balances": balances(),
            "transactions": {
                rng.randbytes(32): rng.randbytes(rng.randrange(90, 140))
                for _ in range(RECEIPTS)
            },
        }
        for _ in range(BLOCKS)
    ]
    return genesis, blocks


def _freeze(state: StateManager, writes) -> object:
    snap = state.new_snapshot()
    for tree, kv in writes.items():
        for k, v in kv.items():
            snap.put(tree, k, v)
    return snap.freeze()


_ORACLE = {}


def _oracle():
    """The Python walker over every block: per block (roots, pending node
    set), and the final leaves of both trees."""
    if not _ORACLE:
        genesis, blocks = _share_blocks()
        state = StateManager(MemoryKV())
        state.commit(0, _freeze(state, genesis))
        per_block = []
        for height, writes in enumerate(blocks, start=1):
            roots = _freeze(state, writes)
            per_block.append((roots, dict(state.trie._pending)))
            state.commit(height, roots)
        leaves = {
            tree: list(state.trie.iter_items(getattr(roots, tree)))
            for tree in ("balances", "transactions")
        }
        _ORACLE.update(genesis=genesis, blocks=blocks, per_block=per_block,
                       leaves=leaves)
    return _ORACLE


@pytest.mark.parametrize("store", ["memory", "lsm_reopened"])
def test_a_share_block_gives_the_python_walkers_bytes(store, tmp_path):
    want = _oracle()
    reopen = store == "lsm_reopened"
    kv = LsmKV(str(tmp_path / "db")) if reopen else MemoryKV()
    state = StateManager(kv, native=True)
    state.commit(0, _freeze(state, want["genesis"]))
    for height, writes in enumerate(want["blocks"], start=1):
        if reopen:
            # a fresh handle over the reopened store: a cold table, so every
            # node the walk needs comes through the loader
            kv.close()
            kv = LsmKV(str(tmp_path / "db"))
            state = StateManager(kv, native=True)
        store_before = _loads("store")
        roots = _freeze(state, writes)
        want_roots, want_pending = want["per_block"][height - 1]
        assert roots == want_roots, (store, height)
        assert dict(state.trie._pending) == want_pending, (store, height)
        if reopen:
            assert _loads("store") > store_before, height
        state.commit(height, roots)
    for tree, leaves in want["leaves"].items():
        assert list(state.trie.iter_items(getattr(roots, tree))) == leaves, tree
    if reopen:
        kv.close()


def _random_trie(seed: int, n: int = 600):
    rng = random.Random(seed)
    keys = [rng.randbytes(rng.randrange(1, 24)) for _ in range(n)]
    kv = MemoryKV()
    trie = Trie(kv)
    root = trie.apply_many(EMPTY_ROOT, {k: rng.randbytes(rng.randrange(0, 48)) for k in keys})
    # deletes collapse branches: reads must follow the collapsed shape too
    root = trie.apply_many(root, {k: None for k in keys[::7]})
    items = trie.peek_pending()
    kv.write_batch(items)
    trie.confirm_pending(items)
    return kv, keys, root, rng


def test_a_read_answers_as_the_python_walk_does():
    kv, keys, root, rng = _random_trie(1)
    absent = [rng.randbytes(9) for _ in range(100)]
    native = Trie(kv, native=True)
    got = {k: native.get(root, k) for k in keys + absent}
    python = Trie(kv)
    want = {k: python.get(root, k) for k in keys + absent}
    assert got == want
    assert all(got[k] is None for k in absent + keys[::7])
    assert native.get(EMPTY_ROOT, keys[1]) is None


def test_a_node_the_store_lacks_still_raises_key_error():
    kv, keys, root, _ = _random_trie(2)
    node_prefix = prefixed(EntryPrefix.TRIE_NODE)
    # drop one node under the root from the store: the subtree below it is
    # unreachable, for the native walks as for the Python ones
    child = next(k for k, v in kv.scan_prefix(node_prefix) if v[:1] == b"I" and k[2:] != root)
    kv.delete(child)
    lost = child[2:].hex()
    for walker in ("native", "python"):
        trie = Trie(kv, native=walker == "native")
        reads = []
        for k in keys:
            try:
                reads.append(trie.get(root, k))
            except KeyError as exc:
                assert lost in str(exc), walker
                break
        else:
            pytest.fail(f"{walker}: no read reached the lost node")
        with pytest.raises(KeyError, match=lost):
            trie.apply_many(root, {k: b"x" for k in keys})
        # the native walk buffers nothing of a failed batch; the Python
        # walk keeps the nodes it stored before the miss (content-addressed
        # orphans, which shrink reclaims like those of a dropped emulation)
        if walker == "native":
            assert trie._pending == {}


def test_a_failed_native_walk_leaves_nothing_in_the_table():
    """A walk that meets a missing node stores nothing anywhere: once the
    node is back, the same batch gives the Python walker's root and
    nodes."""
    kv, keys, root, _ = _random_trie(3)
    node_prefix = prefixed(EntryPrefix.TRIE_NODE)
    child = next(k for k, v in kv.scan_prefix(node_prefix) if v[:1] == b"I" and k[2:] != root)
    enc = kv.get(child)
    kv.delete(child)
    writes = {k: b"y" for k in keys}
    trie = Trie(kv, native=True)
    lib = trie_mod.native_walks()

    def entries():
        out = (ctypes.c_uint64 * 3)()
        lib.lt_trie_size(trie._table, out)
        return out[0]

    assert trie.get(root, b"absent") is None  # the table exists, a path in it
    before, store = entries(), _loads("store")
    with pytest.raises(KeyError):
        trie.apply_many(root, dict(writes))
    # the table grew by what the walk loaded, and by none of what it built
    assert entries() - before == _loads("store") - store > 0
    assert trie._pending == {}
    kv.put(child, enc)
    got = trie.apply_many(root, dict(writes))
    oracle = Trie(kv)
    assert got == oracle.apply_many(root, dict(writes))
    assert trie._pending == oracle._pending


def test_a_loader_error_reaches_the_caller():
    _, keys, root, _ = _random_trie(4)

    class Broken(MemoryKV):
        def get(self, key):
            raise OSError("disk gone")

    trie = Trie(Broken(), native=True)
    with pytest.raises(OSError, match="disk gone"):
        trie.get(root, keys[0])
    with pytest.raises(OSError, match="disk gone"):
        trie.apply_many(root, {keys[0]: b"z"})


def test_a_small_bound_costs_loads_not_answers(monkeypatch):
    """A table far smaller than the tree evicts what a walk needs next:
    the loader brings it back, and nothing the walks answer changes."""
    monkeypatch.setattr(trie_mod, "NODE_TABLE_BYTES", 16 << 10)
    rng = random.Random(5)
    keys = [rng.randbytes(12) for _ in range(800)]
    small, big = Trie(MemoryKV(), native=True), Trie(MemoryKV())
    root_s = root_b = EMPTY_ROOT
    store = _loads("store")
    for step in range(4):
        writes = {k: rng.randbytes(16) for k in rng.sample(keys, 300)}
        writes.update({k: None for k in rng.sample(keys, 40)})
        root_s = small.apply_many(root_s, dict(writes))
        root_b = big.apply_many(root_b, dict(writes))
        assert root_s == root_b, step
        assert small._pending == big._pending, step
    assert _loads("store") > store
    sample = rng.sample(keys, 200)
    want = [big.get(root_b, k) for k in sample]
    assert [small.get(root_s, k) for k in sample] == want


def test_reads_on_one_thread_while_another_freezes():
    """The pool reads the state on the loop's thread while the block tail
    freezes on its own: the table's lock keeps both answers right."""
    rng = random.Random(6)
    keys = [rng.randbytes(16) for _ in range(2000)]
    values = {k: rng.randbytes(20) for k in keys}
    trie = Trie(MemoryKV(), native=True)
    root = trie.apply_many(EMPTY_ROOT, dict(values))
    errors = []

    def reader():
        try:
            for _ in range(3):
                for k in keys[:600]:
                    assert trie.get(root, k) == values[k]
        except BaseException as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    th = threading.Thread(target=reader)
    th.start()
    later = root
    for step in range(6):
        later = trie.apply_many(later, {k: b"%d" % step for k in rng.sample(keys, 500)})
    th.join()
    assert not errors, errors
    python = Trie(MemoryKV())
    python.absorb_pending(trie.export_pending())
    assert all(python.get(root, k) == values[k] for k in keys[:50])


def test_the_load_counter_says_where_the_nodes_came_from():
    """A cold handle's first block loads from the store; a second block of
    the same shape reads what the table kept; a trie that absorbs another's
    buffer reads it from its table without the store."""
    rng = random.Random(7)
    keys = [rng.randbytes(20) for _ in range(3000)]
    kv = MemoryKV()
    seed = Trie(kv)
    root = seed.apply_many(EMPTY_ROOT, {k: rng.randbytes(32) for k in keys})
    items = seed.peek_pending()
    kv.write_batch(items)
    seed.confirm_pending(items)

    trie = Trie(kv, native=True)  # cold: an empty table over a full store

    def block():
        nonlocal root
        store, table = _loads("store"), _loads("table")
        for k in keys[:1000]:
            trie.get(root, k)
        root = trie.apply_many(root, {k: rng.randbytes(32) for k in keys[:1000]})
        trie._flush_loads()
        return _loads("store") - store, _loads("table") - table

    cold_store, cold_table = block()
    assert cold_store > 1000  # every leaf read, and the interior above them
    warm_store, warm_table = block()
    assert warm_store == 0
    assert warm_table > cold_store

    # a consumer over the same store absorbs the producer's buffer (the
    # emulation memo's hand-over) and reads the new root from its table
    nxt = trie.apply_many(root, {k: rng.randbytes(32) for k in keys[:1000]})
    consumer = Trie(kv, native=True)
    consumer.absorb_pending(trie.export_pending())
    store = _loads("store")
    for k in keys[:1000]:
        consumer.get(nxt, k)
    assert _loads("store") == store


def _bound(trie: Trie):
    """(bytes charged, the table's bound) of a native trie's table."""
    out = (ctypes.c_uint64 * 3)()
    trie._walks.lt_trie_size(trie._table, out)
    return out[1], out[2]


def test_the_tables_of_a_process_share_one_budget(monkeypatch):
    """Every live table holds at most the budget over the live tables: the
    64 validators of one process hold what one would alone, and a table
    freed gives its share back."""
    budget = 256 << 10
    monkeypatch.setattr(trie_mod, "NODE_TABLE_BYTES", budget)
    gc.collect()  # free the tables of earlier tests' tries
    rng = random.Random(8)
    tries = [Trie(MemoryKV(), native=True) for _ in range(4)]
    roots = [
        t.apply_many(EMPTY_ROOT, {rng.randbytes(16): rng.randbytes(40) for _ in range(2000)})
        for t in tries
    ]
    # a table trims to its share at the end of its next call: the first
    # filled while it was alone
    for t, root in zip(tries, roots):
        assert t.get(root, b"absent") is None
    live = budget // _bound(tries[0])[1]  # these four, and any still alive
    assert live >= 4
    for t in tries:
        held, bound = _bound(t)
        assert bound == budget // live, bound
        assert 0 < held <= bound
    # half the tables go: the rest may hold more, and do once their next
    # walk stores and loads past the old share
    del tries[2:], roots[2:]
    gc.collect()
    for t, root in zip(tries, roots):
        t.apply_many(root, {rng.randbytes(16): b"x" for _ in range(50)})
        held, bound = _bound(t)
        assert bound == budget // (live - 2), bound
        assert budget // live < held <= bound


def test_a_library_without_the_walks_raises(monkeypatch):
    """A build of the native library that lacks lt_trie_* (a stale one)
    is an error, not a quiet fall back to the Python walker."""
    monkeypatch.setattr(trie_mod, "_native_cache", [False, None])
    monkeypatch.setattr(hashes_mod, "_native_lib", lambda: SimpleNamespace())
    with pytest.raises(RuntimeError, match="lt_trie"):
        Trie(MemoryKV(), native=True)
    # where the library is absent, a native trie walks in Python
    monkeypatch.setattr(trie_mod, "_native_cache", [False, None])
    monkeypatch.setattr(hashes_mod, "_native_lib", lambda: None)
    assert Trie(MemoryKV(), native=True)._walks is None


def test_the_reference_reexecutes_a_validators_chain_on_the_python_walker(
    monkeypatch,
):
    """A validator's state walks natively (devnet.make_node; Node builds its
    state the same way), while perfbench/reference.py re-executes its
    blocks on the Python walker: the benchmark's state-root check compares
    two walkers, not the native walk with itself."""
    chain_id, n = 225, 4
    keys, _ = devnet.devnet_keys(n, 1, 47)
    privs = [(i + 1).to_bytes(32, "big") for i in range(8)]
    addrs = [ecdsa.address_from_public_key(ecdsa.public_key_bytes(k)) for k in privs]
    balances = {a: 10**20 for a in addrs}
    node = devnet.make_node(0, MemoryKV(), keys, chain_id, dict(balances), 100)
    assert node.state.trie._walks is not None
    chain = []
    for height in (1, 2):
        txs = [
            sign_transaction(
                Transaction(to=addrs[(i + 1) % 8], value=7 + i, nonce=height - 1,
                            gas_price=1, gas_limit=21000),
                k, chain_id,
            )
            for i, k in enumerate(privs)
        ]
        txs = BlockManager.order_transactions(txs, chain_id)
        em = node.block_manager.emulate(txs, height)
        assert all(r.status == 1 for r in em.receipts)
        node.state.commit(height, em.roots)
        header = SimpleNamespace(index=height, state_hash=em.state_hash)
        chain.append((SimpleNamespace(header=header), txs))
    assert node.state.trie._table is not None  # the blocks froze natively

    native_calls = []
    real = Trie._apply_native

    def spy(self, root, writes):
        native_calls.append(len(writes))
        return real(self, root, writes)

    monkeypatch.setattr(Trie, "_apply_native", spy)
    assert reference.reexecute(chain_id, balances, keys.ecdsa_pub_keys, chain) == []
    assert native_calls == []
    # and the check bites: a root the Python walker does not reach fails
    wrong_header = SimpleNamespace(index=1, state_hash=b"\x11" * 32)
    bad = [(SimpleNamespace(header=wrong_header), chain[0][1])]
    assert reference.reexecute(chain_id, balances, keys.ecdsa_pub_keys, bad)
