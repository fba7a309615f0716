"""Validator 0 alone in an N-member committee whose other N-1 members are
the honest committee script (consensus/committee_script.py), reached
through the native engine's seam (consensus/native_rt.py `committee=`).

With the same peer proposals it commits, hash for hash, the blocks a full
Devnet of real peers commits; the plain reference (perfbench/
reference_share.py) derives the same blocks and coins from the master
secrets; a wrong decryption or coin share from one peer is convicted and the
era still commits; and the Devnet every validator of which the engine hosts
(hb64-sim's) gives the blocks it gave before the seam existed."""
import random

import pytest

from lachain_tpu.consensus import native_rt
from lachain_tpu.consensus.committee_script import CommitteeScript
from lachain_tpu.core.block_producer import decode_tx_batch
from lachain_tpu.core.devnet import CommitteeValidator, Devnet, devnet_keys
from lachain_tpu.core.types import Transaction, sign_transaction
from lachain_tpu.crypto import ecdsa
from perfbench.reference_share import ShareReference, master_secret, multisig_failures

CHAIN = 225
SEED = 41
ERAS = 2
SIZES = [(7, 2), (16, 5)]


def _clients(count=6, seed=5):
    rng = random.Random(seed)

    class _Rng:
        def randbelow(self, k):
            return rng.randrange(k)

    return [ecdsa.generate_private_key(_Rng()) for _ in range(count)]


def _client_txs(keys, per_key=3):
    return [
        sign_transaction(
            Transaction(
                to=bytes([1 + i]) * 20, value=7, nonce=nonce, gas_price=1,
                gas_limit=21000,
            ),
            k,
            CHAIN,
        )
        for nonce in range(per_key)
        for i, k in enumerate(keys)
    ]


def _balances(keys):
    return {
        ecdsa.address_from_public_key(ecdsa.public_key_bytes(k)): 10**20
        for k in keys
    }


def _real_devnet(n, f, txs_per_block):
    """A Devnet of n real validators (the hb64-sim driver's engine and
    batchers): its blocks and every slot's proposal, by era."""
    keys = _clients()
    net = Devnet(
        n, f, chain_id=CHAIN, seed=SEED, txs_per_block=txs_per_block,
        initial_balances=_balances(keys), engine="native", rbc_batch=True,
    )
    try:
        for stx in _client_txs(keys):
            assert net.submit_tx(stx)
        blocks, proposals = [], {}
        for era in range(1, ERAS + 1):
            blocks.append(net.run_era(era)[0])
            proposals[era] = dict(net.net.routers[0].hb_host(era).result)
        return blocks, proposals
    finally:
        net.close()


def _committee(n, f, txs_per_block, proposals=None, faults=(), balances=None):
    keys = _clients()
    pub, priv = devnet_keys(n, f, SEED)
    script = CommitteeScript(
        pub, priv, chain_id=CHAIN, seed=SEED, txs_per_block=txs_per_block,
        eras=ERAS, proposals=proposals, faults=faults,
    )
    script.setup()
    v = CommitteeValidator(
        pub, priv[0], script, chain_id=CHAIN, seed=SEED,
        txs_per_block=txs_per_block,
        initial_balances=balances if balances is not None else _balances(keys),
    )
    for stx in _client_txs(keys):
        assert v.submit_tx(stx)
    return v, script, pub, priv


@pytest.mark.parametrize("n,f", SIZES)
def test_validator_under_the_script_commits_the_blocks_real_peers_commit(n, f):
    txs_per_block = 4 * n
    real, proposals = _real_devnet(n, f, txs_per_block)
    v, script, pub, priv = _committee(
        n, f, txs_per_block, proposals=lambda era, slot: proposals[era][slot]
    )
    try:
        ref = ShareReference(priv, f, CHAIN)
        for era, want in enumerate(real, start=1):
            block = v.run_era(era)
            assert block.hash() == want.hash()
            assert block.tx_hashes == want.tx_hashes and block.tx_hashes
            # the reference, from the master secrets: the same plaintexts,
            # the same block, the same coins
            expected = ref.block(era, script.ciphertexts(era))
            assert expected.plaintexts == proposals[era]
            coins = v.router.coin_values(era)
            assert len(coins) == n + 1
            assert ref.compare(era, block, expected, coins) == []
            assert ref.compare(era, want, expected, coins) == []
            assert multisig_failures(block, pub.ecdsa_pub_keys, n - f) == []
        assert script.problems == []
    finally:
        v.close()


@pytest.mark.parametrize("n,f", SIZES)
def test_peers_propose_their_own_transfers_and_every_proposal_executes(n, f):
    txs_per_block = 4 * n
    pub, priv = devnet_keys(n, f, SEED)
    probe = CommitteeScript(
        pub, priv, chain_id=CHAIN, seed=SEED, txs_per_block=txs_per_block, eras=ERAS
    )
    balances = {**_balances(_clients()), **probe.peer_balances()}
    v, script, pub, priv = _committee(n, f, txs_per_block, balances=balances)
    try:
        ref = ShareReference(priv, f, CHAIN)
        for era in range(1, ERAS + 1):
            block = v.run_era(era)
            plaintexts = ref.block(era, script.ciphertexts(era)).plaintexts
            assert sorted(plaintexts) == list(range(n))
            for slot in range(1, n):
                batch = decode_tx_batch(plaintexts[slot])
                assert len(batch) == txs_per_block // n
                assert {stx.tx.nonce for stx in batch} == {era - 1}
            peer_txs = (n - 1) * (txs_per_block // n)
            assert len(block.tx_hashes) >= peer_txs
            for h in block.tx_hashes:
                assert v.node.block_manager.receipt_by_hash(h) is not None
    finally:
        v.close()


@pytest.mark.parametrize("proto,index", [("dec", (1,)), ("coin", (0, 5)), ("coin", (-1, 0))])
def test_one_wrong_share_is_convicted_and_the_era_commits(proto, index):
    n, f = 7, 2
    era, liar = 1, 1  # a low id: its share is among the first f + 1 combined
    v, script, pub, priv = _committee(n, f, 4 * n, faults=[(proto, era, index, liar)])
    try:
        block = v.run_era(era)
        records = v.router.evidence.records(era)
        assert [(r.offender, r.proto, r.index) for r in records] == [
            (liar, proto, index)
        ]
        ref = ShareReference(priv, f, CHAIN)
        expected = ref.block(era, script.ciphertexts(era))
        assert ref.compare(era, block, expected, v.router.coin_values(era)) == []
    finally:
        v.close()


# hb64-sim's path (every validator in the engine, native engine, batched RBC)
# on the tree before the seam existed: block hashes by (n, era)
DEVNET_BLOCKS = {
    (7, 1): "5645233f91d2a8433b82eb6124db367f0d1014bf609a8d57dc53e213fc636dbc",
    (7, 2): "92ccf7fb77bc35779fbb1996071824384e9e57524f79fecc3c8d77c669fcf13e",
    (16, 1): "0c7f7765e1d9fff55db00263924fad39f13de95bbc43776d1f61f22a9e9f31ba",
    (16, 2): "ce27a5d2b1548af7cb21eb8acdafd5a14505f0dc2e58295be93126c2f63254e3",
}


@pytest.mark.parametrize("n,f", SIZES)
def test_devnet_of_hosted_validators_gives_the_blocks_it_gave_before(n, f):
    blocks, _proposals = _real_devnet(n, f, 4 * n)
    got = {(n, era): b.hash().hex() for era, b in enumerate(blocks, start=1)}
    assert got == {k: h for k, h in DEVNET_BLOCKS.items() if k[0] == n}


def test_seam_records_round_trip():
    rec = native_rt.encode_seam_record(
        3, -1, native_rt.MT_ECHO, agreement=2, epoch=0, value=0, shard_index=3,
        root=b"r" * 32, branch=[b"a" * 32, b"", b"b" * 32], data=b"shard", era=9,
    )
    (got,) = native_rt.decode_seam_records(rec)
    assert got == native_rt.SeamRecord(
        3, -1, 9, native_rt.MT_ECHO, 2, 0, 0, 0, 3, b"r" * 32,
        (b"a" * 32, b"", b"b" * 32), b"shard",
    )
    assert native_rt.decode_seam_records(rec + rec) == [got, got]


def test_engine_queues_only_remote_to_local_records():
    n, f = 4, 1
    pub, priv = devnet_keys(n, f, SEED)

    class Silent:
        def react(self, records):
            return []

    net = native_rt.NativeSimulatedNetwork(pub, priv[:1], era=1, committee=Silent())
    try:
        lib, h = net._lib, net._h
        bval = dict(agreement=0, epoch=0, value=1)
        ok = native_rt.encode_seam_record(2, 0, native_rt.MT_BVAL, **bval)
        assert lib.rt_inject(h, 1, ok, len(ok)) == 1
        for bad in (
            native_rt.encode_seam_record(0, 0, native_rt.MT_BVAL, **bval),  # local sender
            native_rt.encode_seam_record(2, 3, native_rt.MT_BVAL, **bval),  # remote target
            native_rt.encode_seam_record(2, 0, 9, **bval),  # no such type
            ok[:-2],  # cut short
        ):
            assert lib.rt_inject(h, 1, bad, len(bad)) == 0
        assert lib.rt_queue_len(h) == 1
        assert len(net.routers) == 1
    finally:
        net.close()


def test_script_out_of_tables_is_a_problem_not_an_answer():
    n, f = 4, 1
    pub, priv = devnet_keys(n, f, SEED)
    script = CommitteeScript(pub, priv, chain_id=CHAIN, seed=SEED, txs_per_block=8, eras=1)
    script.setup()
    rec = native_rt.SeamRecord(0, -1, 2, native_rt.MT_BVAL, 0, 0, 1, 0, 0, b"", (), b"")
    assert script.react([rec]) == []
    assert script.problems == ["the committee script holds eras 1..1, not era 2"]
    (answer,) = script.react([rec._replace(era=1)])
    assert answer[0] == 1 and answer[2] == n - 1
    # tables added later are the ones set-up would have built
    script.extend(2)
    whole = CommitteeScript(pub, priv, chain_id=CHAIN, seed=SEED, txs_per_block=8, eras=2)
    whole.setup()
    assert script.eras == 2 and script.tables == whole.tables
    (answer,) = script.react([rec])
    assert answer[0] == 2 and answer[2] == n - 1


def test_master_secret_interpolates_the_dealt_shares():
    from lachain_tpu.crypto import bls12381 as bls

    t, secret = 3, 123456789
    coeffs = [secret, 11, 22, 33]
    shares = [bls.fr_eval_poly(coeffs, i + 1) for i in range(10)]
    assert master_secret(shares, t) == secret


def test_tables_built_in_worker_processes_equal_those_built_in_process():
    n, f = 7, 2
    pub, priv = devnet_keys(n, f, SEED)
    kw = dict(chain_id=CHAIN, seed=SEED, txs_per_block=4 * n, eras=2)
    here = CommitteeScript(pub, priv, **kw)
    here.setup()
    there = CommitteeScript(pub, priv, workers=2, **kw)
    there.start()
    assert there.join() >= 0.0
    assert there.tables == here.tables


@pytest.mark.parametrize("group", ["g1", "g2"])
def test_fixed_base_multiplication_matches_one_multiplication_at_a_time(group):
    from lachain_tpu.crypto import bls12381 as bls
    from lachain_tpu.crypto.native_backend import NativeBackend

    host = NativeBackend()
    rng = random.Random(group)
    scalars = [0, 1, 15, 16, bls.R - 1] + [rng.randrange(bls.R) for _ in range(11)]
    if group == "g1":
        base = host.g1_mul(bls.G1_GEN, 7)
        wire, one = bls.g1_to_bytes(base), lambda s: bls.g1_to_bytes(host.g1_mul(base, s))
    else:
        base = host.g2_mul(bls.G2_GEN, 7)
        wire, one = bls.g2_to_bytes(base), lambda s: bls.g2_to_bytes(host.g2_mul(base, s))
    want = [one(s) for s in scalars]
    assert host.mul_fixed_base(wire, scalars) == want
    assert host.mul_fixed_base(wire, scalars, threads=1) == want
