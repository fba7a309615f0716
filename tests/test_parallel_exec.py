"""Optimistic lane-parallel execution tests.

The contract under test (core/parallel_exec.py): for ANY ordered block,
the lane/merge pipeline produces receipts, frozen roots and trie node
sets bit-identical to the serial oracle — the only thing parallelism may
change is wall-clock. Pinned here by a randomized differential over
transfers, failing txs, system-contract calls and wasm invocations with
engineered conflicts, plus directed tests for the merge validator, the
lane planner, the delta-checkpoint undo log and the sharded pool.
"""
import random
import threading

import pytest

from lachain_tpu.core import block_manager as bm_mod
from lachain_tpu.core import execution, system_contracts
from lachain_tpu.core.block_manager import BlockManager
from lachain_tpu.core.parallel_exec import (
    MIN_PARALLEL_TXS,
    RecordingSnapshot,
    execute_block_parallel,
    plan_lanes,
    resolve_lanes,
)
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


def _run_serial(ordered):
    state, executer, base, _ = _fresh_chain()
    snap = state.new_snapshot(base)
    receipts = [
        executer.execute(snap, stx, 1, i).receipt
        for i, stx in enumerate(ordered)
    ]
    roots = snap.freeze()
    nodes = {k for k, _ in state.trie.peek_pending()}
    return receipts, roots, nodes


def _run_parallel(ordered, n_lanes, partition=None):
    state, executer, base, _ = _fresh_chain()
    merged, receipts, stats = execute_block_parallel(
        executer, state, ordered, 1, base, n_lanes, partition=partition
    )
    roots = merged.freeze()
    nodes = {k for k, _ in state.trie.peek_pending()}
    return receipts, roots, nodes, stats


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
# the headline differential: parallel == serial, bit for bit
# ---------------------------------------------------------------------------


def test_differential_parallel_vs_serial_randomized():
    """>=200 seeded random blocks: receipts, state roots AND the trie
    node set must be bit-identical between the serial oracle and the
    lane/merge pipeline at random lane counts."""
    total_validated = total_stragglers = 0
    _, _, _, caddr = _fresh_chain()
    for seed in range(200):
        rng = random.Random(seed)
        ordered = _random_block(rng, caddr)
        s_receipts, s_roots, s_nodes = _run_serial(ordered)
        # the footprint planner is conservative (overlapping accounts
        # coalesce into one lane), so every third block ignores it and
        # scatters txs round-robin — the adversarial placement that makes
        # the merge validator actually catch cross-lane conflicts
        partition = (lambda i, stx: i) if seed % 3 == 0 else None
        p_receipts, p_roots, p_nodes, stats = _run_parallel(
            ordered, rng.randint(2, 4), partition=partition
        )
        assert [r.encode() for r in p_receipts] == [
            r.encode() for r in s_receipts
        ], f"receipt divergence at seed {seed}"
        assert p_roots == s_roots, f"root divergence at seed {seed}"
        assert p_roots.state_hash() == s_roots.state_hash()
        assert p_nodes == s_nodes, f"trie node set divergence at seed {seed}"
        total_validated += stats.validated
        total_stragglers += stats.stragglers
        assert stats.validated + stats.stragglers == stats.txs
    # the mix must exercise BOTH merge outcomes or the test proves nothing
    assert total_validated > 0
    assert total_stragglers > 0


def test_forced_full_conflict_degrades_to_one_serial_pass():
    """partition= forces a single sender's nonce chain round-robin across
    lanes: every tx after the first fails lane validation. Degradation
    contract: stragglers re-execute at most once (== one serial pass) and
    the result is STILL bit-identical to the oracle."""
    priv, _ = _ACCOUNTS[1]
    to = _ACCOUNTS[2][1]
    ordered = BlockManager.order_transactions(
        [_tx(priv, to, 10 + i, i) for i in range(40)], CHAIN
    )
    s_receipts, s_roots, s_nodes = _run_serial(ordered)
    p_receipts, p_roots, p_nodes, stats = _run_parallel(
        ordered, 4, partition=lambda i, stx: i
    )
    # tx0 read the base state and validates; every other tx read a stale
    # nonce in its lane and re-executes exactly once
    assert stats.validated == 1
    assert stats.stragglers == len(ordered) - 1
    assert stats.stragglers <= len(ordered)  # <= one serial pass, by count
    assert [r.encode() for r in p_receipts] == [r.encode() for r in s_receipts]
    assert p_roots == s_roots
    assert p_nodes == s_nodes
    assert all(r.status == 1 for r in p_receipts)


def test_block_manager_lanes_bit_identical_and_parallel_path_taken():
    """The emulate() seam: a lanes=4 BlockManager returns the same
    EmulationResult as the lanes=1 oracle on a >= MIN_PARALLEL_TXS block,
    via the actual parallel path (counter increment proves it ran)."""
    priv_a, a = _ACCOUNTS[1]
    priv_b, b = _ACCOUNTS[2]
    n = MIN_PARALLEL_TXS + 8
    txs = [_tx(priv_a, b, 5, i) for i in range(n // 2)]
    txs += [_tx(priv_b, a, 7, i) for i in range(n - n // 2)]
    ordered = BlockManager.order_transactions(txs, CHAIN)

    def emulate_with(lanes):
        state, executer, _, _ = _fresh_chain()
        kv = state._kv
        bm = BlockManager(kv, state, executer, lanes=lanes)
        bm_mod._EMULATE_MEMO.clear()  # both runs share one purity key
        return bm.emulate(ordered, 1)

    before = metrics.counter_value("exec_blocks_parallel_total") or 0
    em_serial = emulate_with(1)
    em_parallel = emulate_with(4)
    after = metrics.counter_value("exec_blocks_parallel_total") or 0
    assert after == before + 1
    assert em_parallel.state_hash == em_serial.state_hash
    assert em_parallel.roots == em_serial.roots
    assert [r.encode() for r in em_parallel.receipts] == [
        r.encode() for r in em_serial.receipts
    ]
    assert em_parallel.event_addrs == em_serial.event_addrs


# ---------------------------------------------------------------------------
# lane planning
# ---------------------------------------------------------------------------


def test_plan_lanes_same_sender_single_lane_in_order():
    priv, _ = _ACCOUNTS[1]
    ordered = [_tx(priv, _ACCOUNTS[2][1], 1, i) for i in range(10)]
    lanes = plan_lanes(ordered, CHAIN, 4)
    populated = [l for l in lanes if l]
    assert len(populated) == 1  # one nonce chain -> one lane
    assert [i for i, _ in populated[0]] == list(range(10))


def test_plan_lanes_transitive_footprints_coalesce():
    # A->X, B->X and B->Y, C->Y: one connected component -> one lane
    pa, _ = _ACCOUNTS[1]
    pb, _ = _ACCOUNTS[2]
    pc, _ = _ACCOUNTS[3]
    x, y = _ACCOUNTS[4][1], _ACCOUNTS[5][1]
    ordered = [
        _tx(pa, x, 1, 0),
        _tx(pb, x, 1, 0),
        _tx(pb, y, 1, 1),
        _tx(pc, y, 1, 0),
    ]
    lanes = plan_lanes(ordered, CHAIN, 4)
    populated = [l for l in lanes if l]
    assert len(populated) == 1
    # disjoint footprints spread across lanes
    ordered2 = [_tx(pa, x, 1, 0), _tx(pc, y, 1, 0)]
    lanes2 = plan_lanes(ordered2, CHAIN, 2)
    assert all(len(l) == 1 for l in lanes2)


def test_plan_lanes_deterministic_and_exhaustive():
    rng = random.Random(42)
    _, _, _, caddr = _fresh_chain()
    ordered = _random_block(rng, caddr)
    a = plan_lanes(ordered, CHAIN, 3)
    b = plan_lanes(ordered, CHAIN, 3)
    assert a == b
    flat = sorted(i for lane in a for i, _ in lane)
    assert flat == list(range(len(ordered)))  # every tx exactly once
    for lane in a:
        assert [i for i, _ in lane] == sorted(i for i, _ in lane)


def test_resolve_lanes():
    assert resolve_lanes(1) == 1
    assert resolve_lanes(3) == 3
    assert resolve_lanes(0) >= 1


# ---------------------------------------------------------------------------
# RecordingSnapshot: the read/write footprint the merge validates
# ---------------------------------------------------------------------------


def _recording_snap():
    state, _, base, _ = _fresh_chain()
    return RecordingSnapshot(state.trie.fork(), base)


def test_recording_snapshot_reads_and_delta():
    snap = _recording_snap()
    a = _ACCOUNTS[1][1]
    snap.begin_tx()
    bal = execution.get_balance(snap, a)  # external read
    execution.set_balance(snap, a, bal - 1)
    execution.get_balance(snap, a)  # own-write read: no dependency
    reads, delta = snap.end_tx()
    assert list(reads) == [("balances", b"b:" + a)]
    assert [(t, k) for t, k, _ in delta] == [("balances", b"b:" + a)]


def test_recording_snapshot_restore_drops_reverted_writes():
    snap = _recording_snap()
    snap.begin_tx()
    cp = snap.checkpoint()
    snap.put("storage", b"k1", b"v1")
    snap.put("storage", b"k1", b"v2")
    snap.restore(cp)
    # a fully reverted write exports NO delta (it would clobber an
    # interleaved lane's write at merge time)...
    reads, delta = snap.end_tx()
    assert delta == []
    snap.begin_tx()
    # ...and a post-restore read of that key IS an external dependency
    assert snap.get("storage", b"k1") is None
    reads, _ = snap.end_tx()
    assert ("storage", b"k1") in reads


def test_recording_snapshot_partial_restore_keeps_live_writes():
    snap = _recording_snap()
    snap.begin_tx()
    snap.put("storage", b"k", b"keep")
    cp = snap.checkpoint()
    snap.put("storage", b"k", b"drop")
    snap.restore(cp)
    _, delta = snap.end_tx()
    assert delta == [("storage", b"k", b"keep")]


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
    bm = BlockManager(state._kv, state, executer, lanes=1)
    priv, _ = _ACCOUNTS[1]
    txs = [_tx(priv, _ACCOUNTS[2][1], 1, i) for i in range(4)]
    bm_mod._EMULATE_MEMO.clear()
    with tracing.span("era", era=7):
        bm.emulate(txs, 7)
    report = tracing.era_report()
    assert "exec" in report["phases"]
    ent = next(e for e in report["eras"] if e["era"] == 7)
    assert ent["phases_s"]["exec"] > 0
    assert "exec" in tracing.era_report_table(report).splitlines()[0]
    # a serial block has no lane pipeline: the row stays whole
    split = ("exec_plan", "exec_lanes", "exec_merge")
    assert all(p in report["phases"] for p in split)
    assert all(ent["phases_s"][p] == 0 for p in split)
    # a block through the lanes splits it into plan / lanes / merge
    bm4 = BlockManager(state._kv, state, executer, lanes=4)
    many = [
        _tx(priv, _ACCOUNTS[2 + i % 3][1], 1, i)
        for i in range(MIN_PARALLEL_TXS)
    ]
    bm_mod._EMULATE_MEMO.clear()
    with tracing.span("era", era=8):
        bm4.emulate(many, 8)
    report = tracing.era_report()
    ent = next(e for e in report["eras"] if e["era"] == 8)
    assert all(ent["phases_s"][p] > 0 for p in split)
    header = tracing.era_report_table(report).splitlines()[0]
    assert all(p in header for p in split)
    lanes = [s for s in tracing.snapshot() if s["name"] == "exec.lanes"][-1]
    merge = [s for s in tracing.snapshot() if s["name"] == "exec.merge"][-1]
    assert lanes["args"]["largest_lane"] == MIN_PARALLEL_TXS  # one sender
    assert lanes["args"]["lanes"] == 1 and merge["args"]["stragglers"] == 0
