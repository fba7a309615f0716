"""Block execution tests.

A block executes on one path: the serial executor over a snapshot, then
`Snapshot.freeze`. Pinned here: over random executor blocks (transfers,
failing txs, system-contract calls, wasm invocations) the deferred freeze
gives receipts, roots and trie node sets bit-identical to the immediate
walk; the delta-checkpoint undo log; canonical ordering; the sharded
pool; the era report's exec row; and BlockManager's one lane.
"""
import random
import threading

import pytest

import lachain_tpu.storage.trie as trie_mod
from lachain_tpu.core import block_manager as bm_mod
from lachain_tpu.core import execution, system_contracts
from lachain_tpu.core.block_manager import BlockManager
from lachain_tpu.core.tx_pool import TransactionPool
from lachain_tpu.core.types import (
    SignedTransaction,
    Transaction,
    sign_transaction,
)
from lachain_tpu.crypto import ecdsa
from lachain_tpu.storage.kv import MemoryKV
from lachain_tpu.storage.state import StateManager
from lachain_tpu.utils import metrics, tracing
from lachain_tpu.utils.serialization import write_u256
from lachain_tpu.vm.vm import deploy_code

from test_vm import SEL_INC, counter_contract

pytestmark = pytest.mark.exec

CHAIN = 225


class Rng:
    def __init__(self, seed):
        self._r = random.Random(seed)

    def randbelow(self, n):
        return self._r.randrange(n)


# one shared account pool: keygen is the expensive part, and the global
# sender memo makes repeated recovery of the same signatures cheap
_ACCOUNTS = []
for _i in range(6):
    _priv = ecdsa.generate_private_key(Rng(1000 + _i))
    _addr = ecdsa.address_from_public_key(ecdsa.public_key_bytes(_priv))
    _ACCOUNTS.append((_priv, _addr))

_DEPLOYER = _ACCOUNTS[0][1]


def _tx(priv, to, value, nonce, gas_price=1, gas_limit=100000, invocation=b""):
    tx = Transaction(
        to=to,
        value=value,
        nonce=nonce,
        gas_price=gas_price,
        gas_limit=gas_limit,
        invocation=invocation,
    )
    return sign_transaction(tx, priv, CHAIN)


def _fresh_chain():
    """Fresh store with every pool account funded and one counter wasm
    contract deployed, all committed at height 0 (so the trie pending
    buffer afterwards holds exactly the block-1 node set)."""
    kv = MemoryKV()
    state = StateManager(kv)
    snap = state.new_snapshot()
    for _, addr in _ACCOUNTS:
        execution.set_balance(snap, addr, 10**18)
    status, caddr = deploy_code(snap, _DEPLOYER, 0, counter_contract())
    assert status == 1
    roots = snap.freeze()
    state.commit(0, roots)
    executer = system_contracts.make_executer(CHAIN)
    return state, executer, roots, caddr


def _run(ordered, monkeypatch, defer_from, thread_from):
    """Block 1 on the serial executor, frozen with the trie's floors at
    `defer_from` ops (MIN_DEFER_OPS) and `thread_from` bytes a level
    (MIN_HASH_THREAD_BYTES) -> (receipts, roots, node keys, nodes the
    deferred route hashed)."""
    state, executer, base, _ = _fresh_chain()
    snap = state.new_snapshot(base)
    receipts = [
        executer.execute(snap, stx, 1, i).receipt
        for i, stx in enumerate(ordered)
    ]
    hashed = metrics.counter_value("trie_nodes_hashed_total") or 0
    with monkeypatch.context() as m:
        m.setattr(trie_mod, "MIN_DEFER_OPS", defer_from)
        m.setattr(trie_mod, "MIN_HASH_THREAD_BYTES", thread_from)
        roots = snap.freeze()
    hashed = (metrics.counter_value("trie_nodes_hashed_total") or 0) - hashed
    nodes = {k for k, _ in state.trie.peek_pending()}
    return receipts, roots, nodes, hashed


def _random_block(rng, caddr, min_txs=24, max_txs=48):
    """Random tx mix: plain transfers between pool accounts (footprints
    overlap), bad-nonce failures, native-token system-contract calls, and
    wasm txs all hammering ONE counter (engineered cross-lane conflict)."""
    sender_ids = rng.sample(
        range(len(_ACCOUNTS)), rng.randint(1, min(4, len(_ACCOUNTS)))
    )
    nonces = {i: 0 for i in sender_ids}
    txs = []
    for _ in range(rng.randint(min_txs, max_txs)):
        si = rng.choice(sender_ids)
        priv, _addr = _ACCOUNTS[si]
        nonce = nonces[si]
        kind = rng.random()
        if kind < 0.50:
            to = _ACCOUNTS[rng.randrange(len(_ACCOUNTS))][1]
            txs.append(_tx(priv, to, rng.randint(1, 1000), nonce))
            nonces[si] += 1
        elif kind < 0.65:
            # stale/future nonce: fails WITHOUT consuming sender state
            txs.append(_tx(priv, _ACCOUNTS[0][1], 1, nonce + 7))
        elif kind < 0.82:
            to = _ACCOUNTS[rng.randrange(len(_ACCOUNTS))][1]
            inv = (
                system_contracts.SEL_TRANSFER
                + to
                + write_u256(rng.randint(1, 100))
            )
            txs.append(
                _tx(
                    priv,
                    system_contracts.NATIVE_TOKEN_ADDRESS,
                    0,
                    nonce,
                    invocation=inv,
                )
            )
            nonces[si] += 1
        else:
            txs.append(
                _tx(priv, caddr, 0, nonce, gas_limit=10**9, invocation=SEL_INC)
            )
            nonces[si] += 1
    rng.shuffle(txs)
    return BlockManager.order_transactions(txs, CHAIN)


# ---------------------------------------------------------------------------
# the headline differential: deferred freeze == immediate walk, bit for bit
# ---------------------------------------------------------------------------


def test_differential_parallel_vs_serial_randomized(monkeypatch):
    """200 seeded random blocks: receipts, state roots AND the trie node
    set must be bit-identical between the immediate per-node walk and the
    deferred level-batched freeze, every other block with each level on
    the hashing threads."""
    failed = succeeded = 0
    _, _, _, caddr = _fresh_chain()
    for seed in range(200):
        rng = random.Random(seed)
        ordered = _random_block(rng, caddr)
        i_receipts, i_roots, i_nodes, i_hashed = _run(
            ordered, monkeypatch, 1 << 30, trie_mod.MIN_HASH_THREAD_BYTES
        )
        d_receipts, d_roots, d_nodes, d_hashed = _run(
            ordered, monkeypatch, 1,
            0 if seed % 2 else trie_mod.MIN_HASH_THREAD_BYTES,
        )
        assert [r.encode() for r in d_receipts] == [
            r.encode() for r in i_receipts
        ], f"receipt divergence at seed {seed}"
        assert d_roots == i_roots, f"root divergence at seed {seed}"
        assert d_roots.state_hash() == i_roots.state_hash()
        assert d_nodes == i_nodes, f"trie node set divergence at seed {seed}"
        # each leg took its route: only the deferred one batch-hashes
        assert i_hashed == 0 and d_hashed == len(d_nodes), seed
        failed += sum(r.status != 1 for r in d_receipts)
        succeeded += sum(r.status == 1 for r in d_receipts)
    # the mix must hold failing and applied txs or the test proves little
    assert failed > 0
    assert succeeded > 0


# ---------------------------------------------------------------------------
# BlockManager's one lane
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lanes", [1, 0, 4])
def test_block_manager_takes_one_lane_only(lanes):
    """`lanes=1` is what perfbench/reference.py passes; any other count
    would ask for a path that no longer exists."""
    state, executer, _, _ = _fresh_chain()
    if lanes == 1:
        bm = BlockManager(state._kv, state, executer, lanes=lanes)
        assert bm.current_height() == 0
    else:
        with pytest.raises(ValueError, match="one lane"):
            BlockManager(state._kv, state, executer, lanes=lanes)


# ---------------------------------------------------------------------------
# Snapshot restore: a reverted write leaves nothing behind
# ---------------------------------------------------------------------------


def test_snapshot_restore_drops_reverted_writes():
    state, _, base, _ = _fresh_chain()
    snap = state.new_snapshot(base)
    a = _ACCOUNTS[1][1]
    was = execution.get_balance(snap, a)
    cp = snap.checkpoint()
    # two writes of one key after the checkpoint: the undo log must pop
    # both to leave the key absent, not at its first written value
    snap.put("storage", b"k1", b"v1")
    snap.put("storage", b"k1", b"v2")
    execution.set_balance(snap, a, was - 1)
    execution.set_balance(snap, a, was - 2)
    snap.restore(cp)
    assert snap._writes["storage"] == {} and snap._writes["balances"] == {}
    # reads fall through to the base roots again
    assert snap.get("storage", b"k1") is None
    assert execution.get_balance(snap, a) == was


# ---------------------------------------------------------------------------
# delta checkpoints (storage/state.py undo log)
# ---------------------------------------------------------------------------


def test_checkpoint_restore_randomized_against_model():
    """Undo-log checkpoints vs a deep-copy model: random nested-LIFO
    checkpoint/restore interleaved with puts/deletes must leave the
    buffer exactly where the deep-copy semantics would."""
    import copy

    state, _, base, _ = _fresh_chain()
    rng = random.Random(7)
    for _round in range(20):
        snap = state.new_snapshot(base)
        model = {t: {} for t in snap._writes}
        stack = []
        trees = ("balances", "storage", "events")
        for _ in range(300):
            op = rng.random()
            if op < 0.55:
                t = rng.choice(trees)
                k = bytes([rng.randrange(8)])
                v = bytes([rng.randrange(256)])
                snap.put(t, k, v)
                model[t][k] = v
            elif op < 0.70:
                t = rng.choice(trees)
                k = bytes([rng.randrange(8)])
                snap.delete(t, k)
                model[t][k] = None
            elif op < 0.85:
                stack.append((snap.checkpoint(), copy.deepcopy(model)))
            elif stack:
                cp, saved = stack.pop()
                snap.restore(cp)
                model = saved
        assert snap._writes == model


def test_checkpoint_nested_lifo():
    state, _, base, _ = _fresh_chain()
    snap = state.new_snapshot(base)
    snap.put("storage", b"a", b"1")
    c1 = snap.checkpoint()
    snap.put("storage", b"a", b"2")
    c2 = snap.checkpoint()
    snap.put("storage", b"a", b"3")
    snap.delete("storage", b"b")
    snap.restore(c2)
    assert snap._writes["storage"] == {b"a": b"2"}
    snap.restore(c1)
    assert snap._writes["storage"] == {b"a": b"1"}
    snap.discard()
    assert snap.checkpoint() == 0


# ---------------------------------------------------------------------------
# canonical ordering (the merge walks this order)
# ---------------------------------------------------------------------------


def test_order_transactions_total_and_shuffle_stable():
    rng = random.Random(11)
    _, _, _, caddr = _fresh_chain()
    txs = list(_random_block(rng, caddr))
    # a tx with a garbage signature has NO recoverable sender: ordered
    # under the canonical b"\xff"*20 key, never crashing the sort
    bad = SignedTransaction(
        tx=Transaction(
            to=caddr, value=1, nonce=0, gas_price=1, gas_limit=100000
        ),
        signature=b"\x00" * 65,
    )
    assert bad.sender(CHAIN) is None
    txs.append(bad)
    baseline = BlockManager.order_transactions(txs, CHAIN)
    for seed in range(10):
        shuffled = list(txs)
        random.Random(seed).shuffle(shuffled)
        assert BlockManager.order_transactions(shuffled, CHAIN) == baseline
    # total order: (sender, nonce, hash) strictly non-decreasing
    keys = [
        (stx.sender(CHAIN) or b"\xff" * 20, stx.tx.nonce, stx.hash())
        for stx in baseline
    ]
    assert keys == sorted(keys)
    assert baseline[-1] is bad  # None sender sorts to the very end


# ---------------------------------------------------------------------------
# sharded pool admission
# ---------------------------------------------------------------------------


def _pool(nonce=0):
    return TransactionPool(MemoryKV(), CHAIN, lambda addr: nonce)


def test_pool_concurrent_add_all_admitted():
    n_threads, per_thread = 8, 25
    privs = [ecdsa.generate_private_key(Rng(2000 + i)) for i in range(n_threads)]
    batches = [
        [_tx(priv, _ACCOUNTS[0][1], 1, n) for n in range(per_thread)]
        for priv in privs
    ]
    pool = _pool()
    results = [None] * n_threads

    def work(ti):
        results[ti] = [pool.add(stx) for stx in batches[ti]]

    threads = [
        threading.Thread(target=work, args=(ti,)) for ti in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(all(r) for r in results)
    assert len(pool) == n_threads * per_thread
    # every admitted tx is proposable and persisted
    assert len(pool.peek(10**6)) == n_threads * per_thread
    assert len(pool.persisted_hashes()) == n_threads * per_thread
    # admission contention is observable
    snap = metrics.histogram_snapshot("txpool_admit_lock_wait_seconds")
    assert snap is not None and snap["count"] >= n_threads * per_thread


def test_pool_sharded_semantics_preserved():
    pool = _pool()
    priv, sender = _ACCOUNTS[1]
    stx = _tx(priv, _ACCOUNTS[2][1], 1, 0, gas_price=2)
    assert pool.add(stx)
    assert not pool.add(stx)  # dedup
    assert not pool.precheck(stx)
    # same (sender, nonce): only a strictly higher fee replaces
    cheaper = _tx(priv, _ACCOUNTS[2][1], 2, 0, gas_price=2)
    richer = _tx(priv, _ACCOUNTS[2][1], 3, 0, gas_price=5)
    assert not pool.add(cheaper)
    assert pool.add(richer)
    assert pool.get(stx.hash()) is None
    assert pool.get(richer.hash()) is richer
    assert len(pool) == 1
    assert pool.next_nonce(sender) == 1
    pool.remove_included([richer.hash()])
    assert len(pool) == 0 and pool.persisted_hashes() == []
    # stale-nonce sanitize still sweeps every shard
    assert pool.add(stx)
    pool._account_nonce_fn = lambda addr: 99
    assert pool.sanitize() == 1
    assert len(pool) == 0


# ---------------------------------------------------------------------------
# observability: the exec phase in the era report
# ---------------------------------------------------------------------------


def test_era_report_has_exec_phase_row():
    assert "exec" in tracing.PHASES
    state, executer, _, _ = _fresh_chain()
    bm = BlockManager(state._kv, state, executer)
    priv, _ = _ACCOUNTS[1]
    txs = [_tx(priv, _ACCOUNTS[2][1], 1, i) for i in range(4)]
    bm_mod._EMULATE_MEMO.clear()
    with tracing.span("era", era=7):
        bm.emulate(txs, 7)
    report = tracing.era_report()
    assert "exec" in report["phases"]
    ent = next(e for e in report["eras"] if e["era"] == 7)
    assert ent["phases_s"]["exec"] > 0
    assert ent["phases_s"]["merkle"] > 0  # the freeze nests inside exec.block
    header = tracing.era_report_table(report).splitlines()[0]
    assert "exec" in header
    # one executor: the block's execution is one column, never split
    assert [p for p in report["phases"] if p.startswith("exec")] == ["exec"]
