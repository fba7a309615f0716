"""RBC's VAL fan-out builds a proposal's Merkle tree once (hashes.merkle_tree)
and reads all N branches from it. The blob the native engine's RbcHost posts
stays byte for byte what one merkle_proof call a branch gave, the span
rbc.merkle says the tree took O(N) hashes, and a devnet with the batched
native RBC commits the Python engine's blocks. CPU: counts and bytes only.
"""
import random
from types import SimpleNamespace

import pytest

from lachain_tpu.consensus.native_hosts import PO_RBC_VALS, RbcHost
from lachain_tpu.crypto import hashes
from lachain_tpu.ops import rs
from lachain_tpu.utils import tracing

from tests.test_native_rt import _mk_devnet


@pytest.fixture(autouse=True)
def _clean():
    tracing.reset_for_tests()
    yield
    tracing.reset_for_tests()


def _host(n, f, era=3):
    posts = []
    net = SimpleNamespace(
        _rt_post=lambda me, op, slot, flag, blob, era: posts.append(
            (me, op, slot, flag, blob, era)
        )
    )
    router = SimpleNamespace(
        my_id=2, n_validators=n, f=f, rbc_batcher=None, _net=net
    )
    return RbcHost(router, era), posts


def _reference_blob(era, n, shards):
    """The blob as the code before merkle_tree built it: the whole tree
    again for every branch."""
    leaves = hashes.keccak256_batch(shards)
    blob = bytearray(era.to_bytes(4, "big"))
    blob += hashes.merkle_root(leaves)
    blob += n.to_bytes(4, "big")
    for i in range(n):
        branch = hashes.merkle_proof(leaves, i)
        blob += len(branch).to_bytes(4, "big")
        for h in branch:
            blob += len(h).to_bytes(4, "big")
            blob += h
        blob += len(shards[i]).to_bytes(4, "big")
        blob += shards[i]
    return bytes(blob)


@pytest.mark.parametrize("n,f", [(7, 2), (64, 21)])
def test_post_vals_blob_is_the_per_branch_reference(n, f):
    host, posts = _host(n, f)
    payload = random.Random(n).randbytes(5000)
    host.on_encode(5, payload)  # no batcher: rs.encode, then _post_vals
    assert len(posts) == 1
    me, op, slot, flag, blob, era = posts[0]
    assert (me, op, slot, flag, era) == (2, PO_RBC_VALS, 5, 0, 3)
    assert blob == _reference_blob(3, n, rs.encode(payload, host.k, n))


@pytest.mark.parametrize("n,f", [(7, 2), (64, 21)])
def test_rbc_merkle_span_once_a_proposal(n, f):
    host, _posts = _host(n, f)
    for slot in range(3):
        host.on_encode(slot, bytes([slot]) * 900)
    spans = [s for s in tracing.snapshot() if s["name"] == "rbc.merkle"]
    assert len(spans) == 3
    for s in spans:
        assert s["cat"] == "engine" and not s["open"]
        # N leaves and N - 1 nodes: under the 2N the tree is held to
        assert s["args"] == {"era": 3, "leaves": n, "hashes": 2 * n - 1}


@pytest.mark.parametrize("n,f", [(4, 1), (7, 2)])
def test_batched_native_devnet_commits_the_python_engines_blocks(n, f):
    """Native engine with the RBC batcher (RbcHost._post_vals) against the
    Python engine (ReliableBroadcast._send_vals): same block hashes, and
    one rbc.merkle span a proposal, each inside an rbc.fanout."""
    py = _mk_devnet("python", n=n, f=f)
    want = [b.hash() for b in py.run_eras(1, 2)]
    tracing.reset_for_tests()
    native = _mk_devnet("native", n=n, f=f, rbc_batch=True)
    try:
        got = [b.hash() for b in native.run_eras(1, 2)]
    finally:
        native.close()
    assert got == want
    snap = tracing.snapshot()
    merkle = [s for s in snap if s["name"] == "rbc.merkle"]
    fanout = [s for s in snap if s["name"] == "rbc.fanout"]
    assert len(merkle) == 2 * n
    for s in merkle:
        assert s["args"]["leaves"] == n and s["args"]["hashes"] <= 2 * n
        assert any(
            o["start"] <= s["start"] and s["end"] <= o["end"] for o in fanout
        )
