"""The one sender resolver (core/types.py `_resolve_senders`).

(a) the regression it exists for: a transaction a validator admitted by
    gossip (`warm_sender_caches`) is recovered ONCE, also when its block is
    decoded anew from the agreed proposals and ordered in `create_header`;
(b) a differential against the pure-Python oracle over a thousand random
    keys and hashes and every irregular signature the wire can carry,
    scalar and batch, with the native library and without it;
(c) what the two caches remember: a chain id, a failed recovery, a bound;
(d) a mixed list costs one native call for its regular misses;
(e) a block decoded anew costs one native call for all its senders,
    whether `create_header` or `execute_block` orders it first.
"""
import os
import random
import subprocess
import sys

import pytest

from lachain_tpu.core import types as T
from lachain_tpu.core.block_manager import BlockManager
from lachain_tpu.core.block_producer import (
    BlockProducer,
    decode_tx_batch,
    encode_tx_batch,
)
from lachain_tpu.core.execution import TransactionExecuter
from lachain_tpu.core.types import (
    MultiSig,
    SignedTransaction,
    Transaction,
    sign_transaction,
    warm_sender_caches,
)
from lachain_tpu.crypto import ecdsa
from lachain_tpu.storage.kv import MemoryKV
from lachain_tpu.storage.state import StateManager
from lachain_tpu.utils import metrics

CHAIN = 77
RECOVERIES = "txpool_sender_recoveries_total"
HITS = "txpool_sender_memo_hits_total"
CALLS = "txpool_sender_recovery_calls_total"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_memo():
    T._SENDER_MEMO.clear()
    yield
    T._SENDER_MEMO.clear()


def _counts():
    return metrics.counter_value(RECOVERIES), metrics.counter_value(HITS)


def _moved(before):
    after = _counts()
    return after[0] - before[0], after[1] - before[1]


def _tx(rng, nonce=None):
    return Transaction(
        to=rng.randbytes(20),
        value=rng.randrange(1 << 64),
        nonce=rng.randrange(1 << 32) if nonce is None else nonce,
        gas_price=rng.randrange(1, 8),
        gas_limit=21000,
    )


def _signed(n, seed, keys=8):
    rng = random.Random(seed)
    privs = [ecdsa.generate_private_key() for _ in range(keys)]
    return [
        sign_transaction(_tx(rng, nonce=i), privs[i % keys], CHAIN)
        for i in range(n)
    ]


def _redecoded(stxs):
    """New objects from wire bytes, as a proposal's arrive: no cache."""
    out = [SignedTransaction.decode(s.encode()) for s in stxs]
    assert all("_sender_cache" not in s.__dict__ for s in out)
    return out


def _oracle(h, sig):
    pub = ecdsa._recover_hash_py(h, sig)
    return None if pub is None else ecdsa.address_from_public_key(pub)


# -- (a) the regression ------------------------------------------------------


def _admit_by_gossip(stxs):
    # Node._on_pool_txs: one gossip message a transaction, a batch of one
    for stx in stxs:
        warm_sender_caches([stx], CHAIN)


def _admit_by_submit(stxs):
    # Node.submit_tx -> TransactionPool.add -> sender()
    for stx in stxs:
        stx.sender(CHAIN)


@pytest.mark.parametrize(
    "admit", [_admit_by_gossip, _admit_by_submit], ids=["gossip", "submit"]
)
def test_an_admitted_block_is_recovered_once(admit):
    n = 12
    admitted = _redecoded(_signed(n, seed=1))
    before = _counts()
    admit(admitted)
    assert _moved(before) == (n, 0)
    # the block's transactions are decoded anew from the agreed proposals
    # (consensus/root_protocol.py _try_sign_header), then ordered
    block = decode_tx_batch(encode_tx_batch(admitted))
    assert all("_sender_cache" not in s.__dict__ for s in block)
    ordered = BlockManager.order_transactions(block, CHAIN)
    assert _moved(before) == (n, n), "the block's signatures were recovered again"
    assert sorted(s.hash() for s in ordered) == sorted(s.hash() for s in block)
    by_hash = {s.hash(): s.sender(CHAIN) for s in admitted}
    assert all(s.sender(CHAIN) == by_hash[s.hash()] for s in ordered)
    # ordering, execution and the pool ask again: the object answers
    BlockManager.order_transactions(block, CHAIN)
    assert _moved(before) == (n, n)


def test_one_call_recovers_a_repeated_signature_once():
    stx = _signed(1, seed=2)[0]
    copies = _redecoded([stx, stx, stx])
    before = _counts()
    warm_sender_caches(copies, CHAIN)
    assert _moved(before) == (1, 0)
    want = _oracle(stx.tx.signing_hash(CHAIN), stx.signature)
    assert [c.sender(CHAIN) for c in copies] == [want] * 3
    assert _moved(before) == (1, 0)


# -- (b) the differential ----------------------------------------------------

_N, _P = ecdsa.N, ecdsa.P


def _be(x):
    return x.to_bytes(32, "big")


def _edge_signatures(rng, sig):
    r, s, v = sig[:32], sig[32:64], sig[64:]
    big_r = _be(rng.randrange(_P - _N, _N))  # r + N >= P
    return [
        bytes(32) + s + v,  # r = 0
        r + bytes(32) + v,  # s = 0
        _be(_N) + s + v,  # r = N
        _be(_N + 1 + rng.randrange(1 << 64)) + s + v,  # r > N
        _be((1 << 256) - 1) + s + v,
        r + _be(_N) + v,  # s = N
        r + _be((1 << 256) - 1) + v,
        big_r + s + b"\x02",  # v & 2 with x >= P
        big_r + s + b"\x03",
        _be(rng.randrange(1, 64)) + s + b"\x02",  # v & 2 with x < P
        _be(rng.randrange(1, 64)) + s + b"\x03",
        r + s + b"\x04",
        r + s + b"\x1b",
        r + s + b"\xff",
        sig[:64],  # 64 bytes
        sig + b"\x00",  # 66 bytes
        b"",
    ]


def _corpus():
    """1,100 (transaction, signing hash, signature): random keys and
    hashes, both parities, and in every tenth place an irregular one."""
    rng = random.Random(37)
    out = []
    edges = []
    for i in range(1100):
        priv = ecdsa.generate_private_key()
        tx = _tx(rng)
        h = tx.signing_hash(CHAIN)
        sig = ecdsa.sign_hash(priv, h)
        kind = i % 10
        if kind == 6:  # a flipped bit of s recovers another key
            bad = bytearray(sig)
            bad[32 + rng.randrange(32)] ^= 1 << rng.randrange(8)
            sig = bytes(bad)
        elif kind == 7:  # of r: another key, or no point at that x
            bad = bytearray(sig)
            bad[rng.randrange(32)] ^= 1 << rng.randrange(8)
            sig = bytes(bad)
        elif kind == 8:  # the other parity: the mirrored point's key
            sig = sig[:64] + bytes([sig[64] ^ 1])
        elif kind == 9:
            if not edges:
                edges = _edge_signatures(rng, sig)
            sig = edges.pop()
        out.append((SignedTransaction(tx, sig), h, sig))
    return out


_CORPUS: list = []
_WANT: dict = {}


def _corpus_slice(step):
    if not _CORPUS:
        _CORPUS.extend(_corpus())
    picked = _CORPUS[::step]
    for _, h, sig in picked:
        if (h, sig) not in _WANT:
            _WANT[(h, sig)] = _oracle(h, sig)
    return picked


@pytest.fixture(params=["native", "python"])
def route(request, monkeypatch):
    """`python` is the state LACHAIN_TPU_ECDSA=python leaves the module in
    (the subprocess case below sets the variable itself). The oracle costs
    30 ms a recovery, so that route takes every seventh item."""
    if request.param == "python":
        monkeypatch.setenv("LACHAIN_TPU_ECDSA", "python")
        monkeypatch.setattr(ecdsa, "_native_lib_cache", [False, None])
        assert ecdsa._native_lib() is None
        return 7
    assert ecdsa._native_lib() is not None
    return 1


@pytest.mark.parametrize("path", ["scalar", "batch"])
def test_the_resolver_agrees_with_the_oracle(route, path):
    picked = _corpus_slice(route)
    want = [_WANT[(h, sig)] for _, h, sig in picked]
    assert sum(w is not None for w in want) > len(want) // 2
    assert sum(w is None for w in want) >= len(want) // 20
    fresh = _redecoded([stx for stx, _, _ in picked])
    before = _counts()
    if path == "batch":
        warm_sender_caches(fresh, CHAIN)
        assert _moved(before) == (len(fresh), 0)
    got = [stx.sender(CHAIN) for stx in fresh]
    assert _moved(before) == (len(fresh), 0)
    assert got == want
    # and the memo answers new objects with the same senders, misses too
    again = _redecoded(fresh)
    if path == "batch":
        warm_sender_caches(again, CHAIN)
    assert [stx.sender(CHAIN) for stx in again] == want
    assert _moved(before) == (len(fresh), len(fresh))


@pytest.mark.parametrize("path", ["scalar", "batch"])
def test_the_address_entry_agrees_with_the_oracle_on_any_hash(route, path):
    """What no transaction can carry but a caller of the entry can: hashes
    of 31 and 33 bytes (the oracle's route), zero, N and above."""
    rng = random.Random(41)
    hashes = (
        [rng.randbytes(31), rng.randbytes(33), b""]
        + [bytes(32), _be(_N), _be(_N + 5), _be((1 << 256) - 1)]
        + [rng.randbytes(32) for _ in range(3)]
    )
    items = []
    for h in hashes:
        signed = h if len(h) == 32 else rng.randbytes(32)
        sig = ecdsa.sign_hash(ecdsa.generate_private_key(), signed)
        items += [(h, sig), (h, sig[:64])]
    want = [_oracle(h, sig) for h, sig in items]
    assert sum(w is not None for w in want) >= 9
    if path == "batch":
        got = ecdsa.recover_address_batch(*zip(*items))
    else:
        got = [ecdsa.recover_address_batch([h], [s])[0] for h, s in items]
    assert got == want


def test_the_environment_name_gives_the_same_senders():
    """LACHAIN_TPU_ECDSA=python in a process of its own: the resolver's
    senders there are this process's native ones."""
    picked = _corpus_slice(37)
    fresh = _redecoded([stx for stx, _, _ in picked])
    warm_sender_caches(fresh, CHAIN)
    assert ecdsa._native_lib() is not None
    code = (
        "import sys\n"
        "from lachain_tpu.core.types import SignedTransaction as S, "
        "warm_sender_caches\n"
        "from lachain_tpu.crypto import ecdsa\n"
        "assert ecdsa._native_lib() is None\n"
        "txs = [S.decode(bytes.fromhex(l)) for l in sys.stdin.read().split()]\n"
        f"warm_sender_caches(txs[::2], {CHAIN})\n"
        f"print(' '.join((t.sender({CHAIN}) or b'').hex() or '-' for t in txs))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        input="\n".join(stx.encode().hex() for stx in fresh),
        capture_output=True,
        text=True,
        cwd=REPO,
        env={**os.environ, "LACHAIN_TPU_ECDSA": "python"},
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    want = [(stx.sender(CHAIN) or b"").hex() or "-" for stx in fresh]
    assert out.stdout.split() == want
    assert "-" in want and len(set(want)) > len(want) // 2


# -- (c) what the caches remember --------------------------------------------


def test_another_chain_id_resolves_again():
    stx = _redecoded(_signed(1, seed=3))[0]
    before = _counts()
    mine = stx.sender(CHAIN)
    assert stx.__dict__["_sender_cache"] == (CHAIN, mine)
    other = stx.sender(CHAIN + 1)
    assert _moved(before) == (2, 0)
    assert other == _oracle(stx.tx.signing_hash(CHAIN + 1), stx.signature)
    assert other != mine
    assert stx.__dict__["_sender_cache"] == (CHAIN + 1, other)
    # the object holds one chain id; the memo holds both
    assert stx.sender(CHAIN) == mine
    warm_sender_caches([stx], CHAIN + 1)
    assert stx.sender(CHAIN + 1) == other
    assert _moved(before) == (2, 2)


@pytest.mark.parametrize("path", ["scalar", "batch"])
def test_an_invalid_signature_is_remembered_as_a_miss(path):
    good = _signed(1, seed=4)[0]
    bad = SignedTransaction(good.tx, bytes(32) + good.signature[32:])  # r = 0

    def resolve(stx):
        if path == "batch":
            warm_sender_caches([stx], CHAIN)
            assert stx.__dict__["_sender_cache"] == (CHAIN, None)
        return stx.sender(CHAIN)

    before = _counts()
    assert resolve(bad) is None
    assert _moved(before) == (1, 0)
    key = (bad.tx.signing_hash(CHAIN), bad.signature)
    assert T._SENDER_MEMO[key] is T._MISS
    assert resolve(bad) is None  # the object remembers None
    assert _moved(before) == (1, 0)
    assert resolve(_redecoded([bad])[0]) is None  # and so does the memo
    assert _moved(before) == (1, 1)


def test_the_memo_is_cleared_at_its_bound(monkeypatch):
    monkeypatch.setattr(T, "_SENDER_MEMO_MAX", 4)
    stxs = _redecoded(_signed(7, seed=5))
    before = _counts()
    for i, stx in enumerate(stxs[:6]):
        stx.sender(CHAIN)
        assert len(T._SENDER_MEMO) == (i + 1 if i < 5 else 1)
    # the sixth insertion found five entries and cleared them first
    first = _redecoded(stxs[:1])[0]
    first.sender(CHAIN)
    assert _moved(before) == (7, 0)
    # a batch clears once, before it fills
    T._SENDER_MEMO.update((i, b"x" * 20) for i in range(5))
    warm_sender_caches(stxs[6:], CHAIN)
    assert len(T._SENDER_MEMO) == 1


# -- (d) a mixed list --------------------------------------------------------


class _SpyLib:
    def __init__(self, lib):
        self._lib = lib
        self.calls = []

    def __getattr__(self, name):
        fn = getattr(self._lib, name)

        def call(*args):
            self.calls.append((name, args))
            return fn(*args)

        return call


def test_a_mixed_list_makes_one_native_call(monkeypatch):
    stxs = _signed(9, seed=6)
    cached, memoised, repeated = stxs[0], stxs[1], stxs[2]
    invalid = SignedTransaction(stxs[3].tx, bytes(32) + stxs[3].signature[32:])
    short = SignedTransaction(stxs[4].tx, stxs[4].signature[:64])
    long_ = SignedTransaction(stxs[5].tx, stxs[5].signature + b"\x01")
    misses = stxs[6:]
    cached.sender(CHAIN)
    memoised.sender(CHAIN)
    mixed = (
        [cached]
        + _redecoded([memoised])
        + _redecoded([misses[0], invalid, short, repeated])
        + _redecoded([misses[1], long_, repeated, misses[2]])
    )
    spy = _SpyLib(ecdsa._native_lib())
    monkeypatch.setattr(ecdsa, "_native_lib", lambda: spy)
    before = _counts()
    calls = metrics.counter_value(CALLS)
    warm_sender_caches(mixed, CHAIN)
    # keys handed to step 3: three misses, the invalid, the repeated one
    # once, the two of irregular length; the memo answered one object
    assert _moved(before) == (7, 1)
    assert metrics.counter_value(CALLS) == calls + 1
    batch = [a for name, a in spy.calls if name == "lt_ec_recover_address_batch"]
    assert len(batch) == 1 and batch[0][2] == 5  # the regular misses, once
    # irregular items take the oracle's route, as before: recover_hash,
    # which hands the library's scalar entry a length it refuses
    scalar = [a for name, a in spy.calls if name == "lt_ec_recover"]
    assert sorted(a[2] for a in scalar) == [64, 66]
    assert len(spy.calls) == 3
    want = [
        _oracle(s.tx.signing_hash(CHAIN), s.signature) for s in mixed
    ]
    assert [s.__dict__["_sender_cache"] for s in mixed] == [
        (CHAIN, w) for w in want
    ]
    assert [w is None for w in want] == [
        False, False, False, True, True, False, False, True, False, False,
    ]
    spy.calls.clear()
    warm_sender_caches(_redecoded(mixed), CHAIN)  # all from the memo now
    assert spy.calls == [] and _moved(before) == (7, 1 + len(mixed))
    warm_sender_caches(mixed, CHAIN)  # all from the objects
    assert spy.calls == [] and _moved(before) == (7, 1 + len(mixed))
    assert metrics.counter_value(CALLS) == calls + 1


# -- (e) a block's senders in one call --------------------------------------

_SENDERS = 20
_PER_SENDER = 16


def _block_of_peers():
    """(the block's transactions, each one's true sender): 320 transfers,
    16 nonces of each of 20 keys, in shuffled order, and two signatures
    no key made (r = 0), as the peers' proposals bring them."""
    rng = random.Random(43)
    privs = [ecdsa.generate_private_key() for _ in range(_SENDERS)]
    addrs = [ecdsa.address_from_public_key(ecdsa.public_key_bytes(p)) for p in privs]
    items = [
        (sign_transaction(_tx(rng, nonce=k), privs[i], CHAIN), addrs[i])
        for i in range(_SENDERS)
        for k in range(_PER_SENDER)
    ]
    for i in (3, 11):
        stx = items[i][0]
        items.append(
            (SignedTransaction(stx.tx, bytes(32) + stx.signature[32:]), None)
        )
    rng.shuffle(items)
    # the oracle names the same senders: one transaction of each key, and
    # both that no key made
    for i in range(_SENDERS):
        stx, want = next(it for it in items if it[1] == addrs[i])
        assert _oracle(stx.tx.signing_hash(CHAIN), stx.signature) == want
    for stx, want in items:
        if want is None:
            assert _oracle(stx.tx.signing_hash(CHAIN), stx.signature) is None
    return [stx for stx, _ in items], [a for _, a in items], addrs


def _chain_of(addrs):
    kv = MemoryKV()
    state = StateManager(kv)
    bm = BlockManager(kv, state, TransactionExecuter(CHAIN))
    bm.build_genesis({a: 10**24 for a in addrs}, CHAIN)
    return bm


def _batch_calls(spy):
    names = [name for name, _ in spy.calls]
    assert set(names) <= {"lt_ec_recover_address_batch"}, names
    return [a[2] for _, a in spy.calls]


@pytest.mark.parametrize("first", ["order", "create_header"])
def test_a_block_decoded_anew_makes_one_native_call(monkeypatch, first):
    """What validator 0 of hb128-share meets each era: a block whose
    transactions it never admitted, decoded from the agreed proposals.
    Ordering it, alone or in create_header, recovers every sender in ONE
    call of the threaded address entry, and orders as the per-object
    sort over the true senders does; ordering again, and executing the
    block after create_header, recover nothing. On a chip too: the
    block is far above the chip route's threshold, and never takes it."""
    from lachain_tpu.crypto import provider

    stxs, senders, addrs = _block_of_peers()
    n = len(stxs)
    block = decode_tx_batch(encode_tx_batch(stxs))
    assert all("_sender_cache" not in s.__dict__ for s in block)
    bm = _chain_of(addrs)
    spy = _SpyLib(ecdsa._native_lib())
    monkeypatch.setattr(ecdsa, "_native_lib", lambda: spy)
    monkeypatch.setattr(ecdsa, "_TPU_RECOVER_MIN", 4)
    monkeypatch.setattr(provider, "device_platform", lambda: "tpu")

    def device(hs, ss):
        raise AssertionError("a block's senders took the chip route")

    monkeypatch.setattr(ecdsa, "_tpu_recover", device)
    before = _counts()
    calls = metrics.counter_value(CALLS)
    if first == "order":
        ordered = BlockManager.order_transactions(block, CHAIN)
    else:
        header = BlockProducer(bm, None, 1, n).create_header(1, block, 1)
        ordered = None
    assert _batch_calls(spy) == [n]
    assert _moved(before) == (n, 0)
    assert metrics.counter_value(CALLS) == calls + 1
    by_hash = {s.hash(): a for s, a in zip(stxs, senders)}
    want = sorted(
        block,
        key=lambda s: (by_hash[s.hash()] or b"\xff" * 20, s.tx.nonce, s.hash()),
    )
    assert want[-2:] == [s for s in want if by_hash[s.hash()] is None]
    assert [s.sender(CHAIN) for s in block] == [by_hash[s.hash()] for s in block]
    again = BlockManager.order_transactions(block, CHAIN)
    assert again == want
    if ordered is not None:
        assert ordered == want
    else:
        assert header.merkle_root == T.tx_merkle_root([s.hash() for s in want])
        # the node executes the block it decoded again: the memo answers
        executed = bm.execute_block(header, _redecoded(block), MultiSig(()))
        assert executed.tx_hashes == tuple(s.hash() for s in want)
        assert _moved(before) == (n, n)
    assert _batch_calls(spy) == [n]
    assert metrics.counter_value(CALLS) == calls + 1
