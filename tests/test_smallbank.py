"""Blockbench's Smallbank contract (lachain_tpu/vm/contracts/smallbank.py)
against the benchmark's VM-free dict model (perfbench/reference_smallbank.py):
both VM tiers, wrap-around, a Zipf block through BlockManager.emulate, a
four-validator devnet, the VM's counters, and the traffic generator of the cell
`hb7-smallbank.full` (perfbench/traffic_smallbank.py).
"""
import json
import os
import random

import pytest

from lachain_tpu.core import execution, system_contracts
from lachain_tpu.core import block_manager as bm_mod
from lachain_tpu.core.block_manager import BlockManager
from lachain_tpu.core.devnet import Devnet
from lachain_tpu.core.types import Transaction, sign_transaction
from lachain_tpu.crypto import ecdsa
from lachain_tpu.storage.kv import MemoryKV
from lachain_tpu.storage.state import StateManager
from lachain_tpu.utils import metrics, tracing
from lachain_tpu.utils.serialization import write_bytes
from lachain_tpu.vm.contracts import smallbank
from lachain_tpu.vm.vm import contract_address
from perfbench import reference_smallbank as ref
from perfbench import traffic_smallbank

CHAIN = 225
M = 1 << 256
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "perfbench", "traffic", "smallbank-full.json")) as _fh:
    MIX = json.load(_fh)
GAS_LIMIT = int(MIX["tx"]["gas_limit"])


class Rng:
    def __init__(self, seed):
        self._r = random.Random(seed)

    def randbelow(self, n):
        return self._r.randrange(n)


PRIV = ecdsa.generate_private_key(Rng(31))
ADDR = ecdsa.address_from_public_key(ecdsa.public_key_bytes(PRIV))
CONTRACT = contract_address(ADDR, 0)


def _signed(nonce, to, invocation, priv=PRIV):
    tx = Transaction(
        to=to, value=0, nonce=nonce, gas_price=1, gas_limit=GAS_LIMIT,
        invocation=invocation,
    )
    return sign_transaction(tx, priv, CHAIN)


def _deployment(priv=PRIV):
    return _signed(
        0,
        system_contracts.DEPLOY_ADDRESS,
        system_contracts.SEL_DEPLOY + write_bytes(smallbank.code()),
        priv,
    )


def _chain():
    """A fresh state with the client funded and the contract deployed by an
    ordinary transaction, committed at height 0."""
    state = StateManager(MemoryKV())
    snap = state.new_snapshot()
    execution.set_balance(snap, ADDR, 10**24)
    executer = system_contracts.make_executer(CHAIN)
    res = executer.execute(snap, _deployment(), 0, 0)
    assert res.ok and res.receipt.return_data == CONTRACT
    roots = snap.freeze()
    state.commit(0, roots)
    return state, executer, roots


def _word(snap, tag, account):
    raw = snap.get("storage", CONTRACT + ref.storage_key(tag, ref.account_id(account)))
    return int.from_bytes(raw, "big") if raw else 0


def _random_calls(seed, count, accounts):
    """Seeded operations over few accounts, with amounts that wrap: small,
    2^256 - 1 and anything in between."""
    rng = random.Random(seed)
    out = []
    for _ in range(count):
        name = rng.choice(list(smallbank.SIGNATURES))
        ids, amount = traffic_smallbank.ACCOUNT_ARGS[name]
        args = [rng.randrange(accounts) for _ in range(ids)]
        if amount:
            args.append(rng.choice([rng.randint(1, 100), M - 1, rng.randrange(M)]))
        out.append((name, tuple(args)))
    return out


_RUNS = {}


def _run_2000(tier, monkeypatch):
    """2,000 operations on 50 accounts through executer.execute on one
    tier; every result checked against the dict model as it goes. Cached: the
    tiers are compared with each other afterwards."""
    if tier in _RUNS:
        return _RUNS[tier]
    if tier == "interp":
        monkeypatch.setenv("LACHAIN_TPU_WASM", "interp")
    else:
        monkeypatch.delenv("LACHAIN_TPU_WASM", raising=False)
    state, executer, roots = _chain()
    snap = state.new_snapshot(roots)
    bank = ref.Bank()
    receipts = []
    counted = lambda: [
        metrics.counter_value(n) for n in ("vm_calls_total", "vm_interpreted_calls_total")
    ]
    before = counted()
    for i, (name, args) in enumerate(_random_calls(5, 2000, 50)):
        calldata = smallbank.encode_call(name, *args)
        res = executer.execute(snap, _signed(1 + i, CONTRACT, calldata), 1, i)
        assert res.ok, (name, args)
        want = bank.apply(calldata)
        got = res.receipt.return_data
        assert got == (b"" if want is None else want.to_bytes(32, "big")), (name, args)
        receipts.append((res.receipt.gas_used, got))
    words = [
        [_word(snap, ref.TAG_SAVING, a), _word(snap, ref.TAG_CHECKING, a)]
        for a in range(50)
    ]
    assert words == bank.balances([ref.account_id(a) for a in range(50)])
    ran = [after - was for after, was in zip(counted(), before)]
    _RUNS[tier] = receipts, words, ran
    return _RUNS[tier]


@pytest.mark.parametrize("tier", ["translated", "interp"])
def test_contract_equals_dict_model_over_2000_operations(tier, monkeypatch):
    receipts, _words, ran = _run_2000(tier, monkeypatch)
    assert len(receipts) == 2000
    # every call is counted once, and as interpreted where the interpreter ran
    assert ran == [2000, 2000 if tier == "interp" else 0]


def test_the_two_tiers_agree_on_balances_return_data_and_gas(monkeypatch):
    fast = _run_2000("translated", monkeypatch)
    slow = _run_2000("interp", monkeypatch)
    assert fast[1] == slow[1]
    assert fast[0] == slow[0], "gas or return data differs between the tiers"
    assert len({gas for gas, _ret in fast[0]}) > 6  # all six operations ran


WRAPS = [
    # (calls, account, saving, checking afterwards)
    ([("writeCheck", (7, 1))], 7, 0, M - 1),  # 0 - 1; not covered: no extra 1
    ([("writeCheck", (7, 1)), ("updateBalance", (7, 1))], 7, 0, 0),  # 2^256 - 1 + 1
    ([("sendPayment", (7, 8, 1))], 7, 0, M - 1),
    ([("sendPayment", (7, 8, 1))], 8, 0, 1),
    ([("sendPayment", (7, 7, 5))], 7, 0, 5),  # a = b leaves + v
    ([("updateSaving", (7, M - 1)), ("updateSaving", (7, 2))], 7, 1, 0),
    # covered: total 10 > 3, so one more goes
    ([("updateSaving", (7, 10)), ("writeCheck", (7, 3))], 7, 10, M - 4),
    ([("updateSaving", (7, 4)), ("updateBalance", (7, 5)), ("almagate", (7, 7))], 7, 9, 0),
    ([("updateSaving", (7, M - 1)), ("updateBalance", (8, 3)), ("almagate", (7, 8))], 8, 2, 3),
]


@pytest.mark.parametrize("calls,account,saving,checking", WRAPS)
def test_arithmetic_is_modulo_2_256(calls, account, saving, checking):
    state, executer, roots = _chain()
    snap = state.new_snapshot(roots)
    bank = ref.Bank()
    for i, (name, args) in enumerate(calls):
        calldata = smallbank.encode_call(name, *args)
        assert executer.execute(snap, _signed(1 + i, CONTRACT, calldata), 1, i).ok
        bank.apply(calldata)
    got = [_word(snap, ref.TAG_SAVING, account), _word(snap, ref.TAG_CHECKING, account)]
    assert got == [saving, checking] == bank.balances([ref.account_id(account)])[0]
    total = smallbank.encode_call("getBalance", account)
    res = executer.execute(snap, _signed(1 + len(calls), CONTRACT, total), 1, 99)
    assert int.from_bytes(res.receipt.return_data, "big") == (saving + checking) % M


_GET = smallbank.encode_call("getBalance", 12345)
BAD_CALLDATA = {
    "no such method": b"\x01\x02\x03\x04" + _GET[4:],
    "the selector alone": _GET[:4],
    "longer than 512 bytes": _GET + b"\0" * 512,
    "offset leaves the calldata": _GET[:4] + (96).to_bytes(32, "big") + _GET[36:],
    "offset above 2^16": _GET[:4] + (1 << 200).to_bytes(32, "big") + _GET[36:],
    "length leaves the calldata": _GET[:36] + (33).to_bytes(32, "big") + _GET[68:],
    "id longer than 64 bytes": smallbank.encode_call("getBalance", b"7" * 65),
}


@pytest.mark.parametrize("tier", ["translated", "interp"])
def test_calldata_the_contract_does_not_know_fails_and_writes_nothing(tier, monkeypatch):
    if tier == "interp":
        monkeypatch.setenv("LACHAIN_TPU_WASM", "interp")
    state, executer, roots = _chain()
    snap = state.new_snapshot(roots)
    for i, (what, bad) in enumerate(BAD_CALLDATA.items()):
        res = executer.execute(snap, _signed(1 + i, CONTRACT, bad), 1, i)
        assert not res.ok and res.receipt.return_data == b"", what
    assert not snap._writes["storage"]
    # and the longest id the module takes, with an offset that is not the
    # canonical one, reads as the source's would
    long_id = b"7" * 64
    call = smallbank.encode_call("updateSaving", long_id, 9)
    moved = call[:4] + (96).to_bytes(32, "big") + call[36:68] + b"\0" * 32 + call[68:]
    assert ref.decode(moved) == ("updateSaving", (long_id, 9), 1)
    assert executer.execute(snap, _signed(8, CONTRACT, moved), 1, 8).ok
    raw = snap.get("storage", CONTRACT + ref.storage_key(ref.TAG_SAVING, long_id))
    assert int.from_bytes(raw, "big") == 9


def test_calldata_is_the_sources_width():
    """smallbank.sol takes string ids: an offset, a length and a padded data
    word each, beside the amount's one word."""
    sizes = {
        name: len(smallbank.encode_call(name, *range(90000, 90000 + ids + amount)))
        for name, (ids, amount) in traffic_smallbank.ACCOUNT_ARGS.items()
    }
    assert sizes == {
        "almagate": 196, "getBalance": 100, "updateBalance": 132,
        "updateSaving": 132, "sendPayment": 228, "writeCheck": 132,
    }
    assert ref.storage_key(b"s", b"90000") == __import__(
        "lachain_tpu.crypto.hashes", fromlist=["keccak256"]
    ).keccak256(b"s90000")


def _zipf_block(count):
    """`count` calls as the cell's mix draws them, all from one sender."""
    ops = traffic_smallbank.operations(MIX, 11)
    return [
        _signed(1 + i, CONTRACT, smallbank.encode_call(name, *args))
        for i, (name, args) in zip(range(count), ops)
    ]


def test_zipf_block_through_emulate_reads_back_the_model():
    """The 200-call block as the cell draws it, through the block path a
    validator runs (BlockManager.emulate, frozen and committed): the store
    reads back the VM-free model's balances and return data."""
    block = _zipf_block(200)
    state, executer, _roots = _chain()
    bm = BlockManager(state._kv, state, executer)
    bm_mod._EMULATE_MEMO.clear()
    em = bm.emulate(block, 1)
    assert all(r.status == 1 for r in em.receipts)
    state.commit(1, em.roots)
    bank = ref.Bank()
    for stx, receipt in zip(block, em.receipts):
        want = bank.apply(stx.tx.invocation)
        assert receipt.return_data == (b"" if want is None else want.to_bytes(32, "big"))
    snap = state.new_snapshot()
    accounts = sorted(bank.touched)
    words = [
        [
            int.from_bytes(snap.get("storage", CONTRACT + ref.storage_key(t, a)) or b"", "big")
            for t in (ref.TAG_SAVING, ref.TAG_CHECKING)
        ]
        for a in accounts
    ]
    assert len(accounts) > 100 and words == bank.balances(accounts)
    spans = [s for s in tracing.snapshot() if s["name"] == "exec.block"]
    assert spans and spans[-1]["args"]["era"] == 1


def test_vm_counters_count_calls_seconds_gas_and_storage_words():
    state, executer, roots = _chain()
    snap = state.new_snapshot(roots)
    names = ("vm_calls_total", "vm_call_seconds_total", "vm_gas_used_total",
             "contract_storage_reads_total", "contract_storage_writes_total")
    read = lambda: {n: sum(metrics.counters_with_prefix(n).values()) for n in names}
    before = read()
    calls = [("almagate", (1, 2)), ("getBalance", (1,)), ("sendPayment", (1, 2, 3))]
    gas = 0
    for i, (name, args) in enumerate(calls):
        res = executer.execute(snap, _signed(1 + i, CONTRACT, smallbank.encode_call(name, *args)), 1, i)
        gas += res.receipt.gas_used - execution.GAS_PER_TX
    moved = {n: v - before[n] for n, v in read().items()}
    assert moved["vm_calls_total"] == 3
    assert moved["contract_storage_reads_total"] == 6  # two words a call
    assert moved["contract_storage_writes_total"] == 4  # 2 + 0 + 2
    assert moved["vm_gas_used_total"] == gas
    assert 0 < moved["vm_call_seconds_total"] < 1


def test_gas_limit_of_the_mix_is_twice_the_dearest_operation():
    state, executer, roots = _chain()
    snap = state.new_snapshot(roots)
    need = 0
    for i, (name, (ids, amount)) in enumerate(traffic_smallbank.ACCOUNT_ARGS.items()):
        # the longest ids the mix draws: a key hash is billed by the byte
        top = int(MIX["bank"]["accounts"])
        args = list(range(top - ids, top)) + ([50] if amount else [])
        res = executer.execute(snap, _signed(1 + i, CONTRACT, smallbank.encode_call(name, *args)), 1, i)
        assert res.ok
        need = max(need, res.receipt.gas_used)
    assert GAS_LIMIT == 2 * need


def test_four_validators_commit_smallbank_calls_and_read_back_the_model():
    keys = [ecdsa.generate_private_key(Rng(900 + i)) for i in range(4)]
    addrs = [ecdsa.address_from_public_key(ecdsa.public_key_bytes(k)) for k in keys]
    net = Devnet(
        n=4, f=1, chain_id=CHAIN, seed=3, txs_per_block=64,
        initial_balances={a: 10**24 for a in addrs}, engine="native",
    )
    try:
        assert net.submit_tx(_deployment(keys[0]))
        net.run_era(1)
        contract = contract_address(addrs[0], 0)
        sent = {}
        ops = traffic_smallbank.operations(MIX, 19)
        for k, (name, args) in zip(range(48), ops):
            who = k % 4
            stx = _signed(k // 4 + (who == 0), contract, smallbank.encode_call(name, *args), keys[who])
            sent[stx.hash()] = stx
            assert net.submit_tx(stx)
        era = 2
        while len(net.nodes[0].pool) and era < 6:
            net.run_era(era)
            era += 1
        calls = []
        for height in range(2, net.height() + 1):
            block = net.nodes[0].block_manager.block_by_height(height)
            calls += [(h, sent[h].tx.invocation) for h in block.tx_hashes]
        assert len(calls) == 48
        bank, returns = ref.replay(calls)
        accounts = sorted(bank.touched)
        for node in net.nodes:
            snap = node.state.new_snapshot()
            words = [
                [
                    int.from_bytes(snap.get("storage", contract + ref.storage_key(t, a)) or b"", "big")
                    for t in (ref.TAG_SAVING, ref.TAG_CHECKING)
                ]
                for a in accounts
            ]
            assert words == bank.balances(accounts)
            for h, value in returns:
                from lachain_tpu.core.types import TransactionReceipt

                receipt = TransactionReceipt.decode(node.block_manager.receipt_by_hash(h))
                assert receipt.status == 1
                assert int.from_bytes(receipt.return_data, "big") == value
    finally:
        net.close()


# -- the reference's own decoding, and the generator -----------------------------


def test_reference_decodes_calldata_without_the_vm():
    for name, sig in smallbank.SIGNATURES.items():
        words, ids = ref.SIGNATURES[sig]
        args = tuple(range(3, 3 + words))
        want = tuple(ref.account_id(a) for a in args[:ids]) + args[ids:]
        assert ref.decode(smallbank.encode_call(name, *args)) == (name, want, ids)
        assert smallbank.account_id(args[0]) == ref.account_id(args[0]) == b"3"
    assert (ref.TAG_SAVING, ref.TAG_CHECKING) == (smallbank.TAG_SAVING, smallbank.TAG_CHECKING)
    with pytest.raises(ValueError):
        ref.decode(smallbank.encode_call("getBalance", 1)[:-32])
    # the model knows no VM: of the program it imports the hash alone
    import ast

    with open(ref.__file__) as fh:
        tree = ast.parse(fh.read())
    imported = {
        node.module if isinstance(node, ast.ImportFrom) else alias.name
        for node in ast.walk(tree)
        if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in node.names
    }
    assert {m for m in imported if m.startswith("lachain_tpu")} == {
        "lachain_tpu.crypto.hashes"
    }


@pytest.mark.parametrize("what", ["word", "return", "missing receipt"])
def test_compare_reports_a_store_that_differs_from_the_model(what):
    words, returns = [[1, 2], [3, 4]], [5, 6]
    report = {"words": [[1, 2], [3, 4]], "returns": [5, 6]}
    assert ref.compare("validator 3", words, returns, report) == []
    if what == "word":
        report["words"][1][0] ^= 1
    else:
        report["returns"][0] = 7 if what == "return" else None
    (finding,) = ref.compare("validator 3", words, returns, report)
    assert finding.startswith("validator 3: 1 of 2 ")


def test_generator_same_seed_same_stream():
    def head(seed, count=40):
        stream = traffic_smallbank.signed_stream(MIX, seed, CHAIN)
        return [next(stream) for _ in range(count)]

    assert head(2147489001) == head(2147489001)
    assert head(2147489001)[1:] != head(2147489002)[1:]
    from lachain_tpu.core.types import SignedTransaction

    first, second = (SignedTransaction.decode(raw) for raw in head(5, 2))
    assert first.tx.to == system_contracts.DEPLOY_ADDRESS and first.tx.nonce == 0
    assert second.tx.to == traffic_smallbank.contract_address(MIX, 5)
    assert second.tx.to == contract_address(first.sender(CHAIN), 0)
    assert ref.decode(second.tx.invocation)[0] in smallbank.SIGNATURES


def test_operations_follow_the_weights_and_the_amount_range():
    ops = traffic_smallbank.operations(MIX, 23)
    drawn = [next(ops) for _ in range(20000)]
    weights = MIX["bank"]["weights"]
    for name, weight in weights.items():
        share = sum(n == name for n, _a in drawn) / len(drawn)
        assert abs(share - weight / 100) < 0.012, name
    for name, args in drawn:
        ids, amount = traffic_smallbank.ACCOUNT_ARGS[name]
        assert len(args) == ids + amount
        assert all(0 <= a < MIX["bank"]["accounts"] for a in args[:ids])
        assert not amount or 1 <= args[-1] <= 100


def test_zipf_top_10_mass_within_2_percent_of_the_closed_form():
    n, constant = int(MIX["bank"]["accounts"]), float(MIX["bank"]["zipf_constant"])
    zipf = traffic_smallbank.Zipf(n, constant)
    closed = sum(r ** -constant for r in range(1, 11)) / sum(
        r ** -constant for r in range(1, n + 1)
    )
    assert zipf.mass(10) == pytest.approx(closed, rel=1e-9)
    rng = random.Random(77)
    draws = [zipf.draw(rng) for _ in range(200000)]
    assert all(0 <= d < n for d in draws)
    top = sum(d < 10 for d in draws) / len(draws)
    assert abs(top - closed) / closed < 0.02
    assert draws.count(0) > draws.count(1) > draws.count(5)
