"""Batched Reed-Solomon engine (ops/rs_batch.py + consensus/rbc_batcher.py).

The batched codec exists to fuse an era's RBC encode/interpolate work into
a handful of GF matrix products, so its one non-negotiable property is
BIT-IDENTITY with the scalar ops/rs.py path: same shards, same payloads,
same None verdicts — under random erasure, adversarial shard substitution,
and every loss count from 1 to N-1. On top sit the era-batcher semantics
(per-(root,k,n) dedupe + verdict memo), the stale-library/env fallbacks,
and the end-to-end anchor: a devnet era produces bit-identical block hashes
with batching on vs off, on BOTH engines.
"""
import random

import pytest

from lachain_tpu.consensus.rbc_batcher import RbcEraBatcher, scalar_verdict
from lachain_tpu.crypto import hashes
from lachain_tpu.ops import rs, rs_batch

pytestmark = pytest.mark.kernel


# --- scalar-vs-batch differential -------------------------------------------


def _erase(shards, rng, lost):
    out = list(shards)
    for i in rng.sample(range(len(out)), lost):
        out[i] = None
    return out


@pytest.mark.parametrize("seed", range(200))
def test_differential_encode_decode_200_seeds(seed):
    """200-seed sweep: batch encode == scalar encode byte-for-byte, and
    batch decode under random erasure returns the scalar verdict."""
    rng = random.Random(seed)
    n = rng.randint(4, 40)
    f = (n - 1) // 3
    k = max(n - 2 * f, 1)
    data = bytes(rng.getrandbits(8) for _ in range(rng.randint(0, 300)))

    scalar = rs.encode(data, k, n)
    [batched] = rs_batch.encode_batch([(data, k, n)])
    assert batched == scalar

    lost = rng.randint(0, n - k)
    shards = _erase(scalar, rng, lost)
    assert rs.decode(shards, k) == data
    [payload] = rs_batch.decode_batch([(shards, k)])
    assert payload == data


@pytest.mark.parametrize("seed", range(40))
def test_differential_adversarial_mismatched_shards(seed):
    """An equivocating sender commits a Merkle root over shards drawn from
    TWO different polynomials. Every shard branch-verifies against that
    root, decode reconstructs a polynomial, but the re-encode + root
    recheck must reject — identically on the scalar and batched paths —
    and the bad verdict must not bleed into an honest root's delivery."""
    rng = random.Random(1000 + seed)
    n = rng.randint(4, 24)
    k = max(n - 2 * ((n - 1) // 3), 1)
    good = bytes(rng.getrandbits(8) for _ in range(64))
    evil = bytes(rng.getrandbits(8) for _ in range(64))
    mixed = list(rs.encode(good, k, n))
    wrong = rs.encode(evil, k, n)
    mixed[rng.randrange(n)] = wrong[rng.randrange(n)]
    if len(mixed[0]) != len(wrong[0]):  # keep shard sizes uniform
        mixed = list(rs.encode(good, k, n))
        mixed[rng.randrange(n)] = bytes(
            x ^ 0x5A for x in mixed[rng.randrange(n)]
        )

    # raw decode differential: garbage payload or None, but the SAME one
    assert rs.decode(mixed, k) == rs_batch.decode_batch([(mixed, k)])[0]

    evil_root = hashes.merkle_root(hashes.keccak256_batch(mixed))
    want = scalar_verdict(mixed, k, evil_root)

    got = []
    b = RbcEraBatcher()
    b.submit_interpolate(0, mixed, k, n, evil_root, got.append)
    b.flush()
    assert got == [want]
    # same root again: the memo answers with the SAME verdict, no reflush
    b.submit_interpolate(0, mixed, k, n, evil_root, got.append)
    assert got[-1] == want and b.flushes == 1
    # an honest sender's root in the same era still delivers
    honest = rs.encode(good, k, n)
    honest_root = hashes.merkle_root(hashes.keccak256_batch(honest))
    b.submit_interpolate(0, honest, k, n, honest_root, got.append)
    b.flush()
    assert got[-1] == good


@pytest.mark.parametrize("lost_kind", ["one", "max", "n_minus_1"])
def test_differential_loss_extremes(lost_kind):
    """Loss extremes: 1 shard, N-K shards (decode still possible), and N-1
    shards (below K — both paths must refuse identically)."""
    rng = random.Random(7)
    n, k = 16, 6
    data = bytes(range(200))
    shards = rs.encode(data, k, n)
    lost = {"one": 1, "max": n - k, "n_minus_1": n - 1}[lost_kind]
    erased = _erase(shards, rng, lost)
    want = data if lost <= n - k else None
    assert rs.decode(erased, k) == want
    assert rs_batch.decode_batch([(erased, k)]) == [want]


def test_batch_grouping_mixed_shapes():
    """One flush mixing (k,n) shapes, fields and erasure patterns returns
    every item's scalar result in submission order."""
    rng = random.Random(99)
    enc_items, dec_items, want_payloads = [], [], []
    for i in range(20):
        n = rng.choice([4, 7, 16, 300])
        k = max(n - 2 * ((n - 1) // 3), 1)
        data = bytes(rng.getrandbits(8) for _ in range(rng.randint(1, 150)))
        enc_items.append((data, k, n))
        shards = _erase(list(rs.encode(data, k, n)), rng, rng.randint(0, n - k))
        dec_items.append((shards, k))
        want_payloads.append(data)
    assert rs_batch.encode_batch(enc_items) == [
        rs.encode(d, k, n) for d, k, n in enc_items
    ]
    assert rs_batch.decode_batch(dec_items) == want_payloads


# --- GF(2^16): past the GF(2^8) wall ----------------------------------------


def test_gf16_round_trip_512_shards():
    """N=512 > 255 forces the GF(2^16) codec: full round trip with the
    maximum tolerated erasure."""
    n = 512
    k = n - 2 * ((n - 1) // 3)
    data = bytes(i % 251 for i in range(5000))
    shards = rs_batch.encode(data, k, n)
    assert len(shards) == n and len(set(shards)) == n
    rng = random.Random(3)
    erased = _erase(list(shards), rng, n - k)
    assert rs_batch.decode(erased, k) == data


def test_gf16_via_rs_facade():
    """ops/rs.py transparently delegates n>255 to the GF(2^16) codec — the
    replication-mode refusal is gone."""
    data = b"past-the-wall" * 9
    shards = rs.encode(data, 100, 300)
    # coded, not replicated: replication mode shipped n identical copies
    assert len(set(shards)) > 1
    erased = list(shards)
    for i in range(150):
        erased[i] = None
    assert rs.decode(erased, 100) == data


def test_gf16_odd_and_mixed_sizes_refused():
    """uint16 symbols: an odd-length shard (or mixed sizes) can only be
    corruption — clean None, no exception."""
    data = bytes(range(100))
    shards = list(rs_batch.encode(data, 90, 280))
    shards[0] = shards[0] + b"x"  # odd length
    assert rs_batch.decode(shards, 90) is None
    shards2 = list(rs_batch.encode(data, 90, 280))
    shards2[1] = shards2[1] + b"xy"  # even but mismatched
    assert rs_batch.decode(shards2, 90) is None


def test_gf16_field_properties():
    gf = rs_batch.gf16()
    assert gf.order == 65535
    for a in (1, 2, 777, 65535):
        assert gf.mul(a, gf.inv(a)) == 1


# --- era batcher semantics ---------------------------------------------------


def test_batcher_dedupes_identical_interpolations():
    """N validators interpolating the same (root,k,n) collapse to ONE codec
    run per flush; every waiter still gets its callback."""
    n, k = 7, 3
    data = b"dedupe-me" * 4
    shards = rs.encode(data, k, n)
    root = hashes.merkle_root(hashes.keccak256_batch(shards))
    b = RbcEraBatcher()
    got = []
    for _ in range(n):
        b.submit_interpolate(1, shards, k, n, root, got.append)
    b.flush()
    assert got == [data] * n
    assert b.flushes == 1


def test_batcher_memo_answers_repeat_roots_without_flush():
    """Within an era, a later submit for an already-settled (root,k,n) is
    answered from the memo immediately — no second codec run."""
    n, k = 7, 3
    data = b"memoized" * 8
    shards = rs.encode(data, k, n)
    root = hashes.merkle_root(hashes.keccak256_batch(shards))
    b = RbcEraBatcher()
    got = []
    b.submit_interpolate(2, shards, k, n, root, got.append)
    b.flush()
    b.submit_interpolate(2, shards, k, n, root, got.append)  # memo hit
    assert got == [data, data]
    assert not b.pending
    assert b.flushes == 1


def test_batcher_flush_is_era_scoped():
    b = RbcEraBatcher()
    got = []
    b.submit_encode(1, b"era1", 2, 4, got.append)
    b.submit_encode(2, b"era2", 2, 4, got.append)
    assert b.pending_for(1) and b.pending_for(2)
    b.flush(1)
    assert len(got) == 1 and not b.pending_for(1) and b.pending_for(2)
    b.flush(2)
    assert len(got) == 2 and not b.pending


# --- fallbacks ---------------------------------------------------------------


def test_native_stale_library_probe_degrades(monkeypatch):
    """A .so without rt_set_rbc_host (stale build): the network must come up
    with the batcher disabled and still run — the engine keeps its
    per-message RS path."""
    from lachain_tpu.consensus import messages as M
    from lachain_tpu.consensus.native_rt import NativeSimulatedNetwork, load_rt
    from tests.test_consensus import keys_for

    monkeypatch.setattr(load_rt(), "_lt_has_rbc_host", False)
    pub, privs = keys_for(4, 1)
    net = NativeSimulatedNetwork(pub, privs, seed=5, use_rbc_batcher=True)
    try:
        assert net.rbc_batcher is None  # probe said no: degraded
        pid = M.HoneyBadgerId(era=0)
        for i in range(4):
            net.post_request(i, pid, b"stale-so-%d" % i)
        assert net.run(
            lambda: all(r.result_of(pid) is not None for r in net.routers)
        )
    finally:
        net.close()


def test_env_kill_switch_disables_batcher(monkeypatch):
    from lachain_tpu.consensus.native_rt import NativeSimulatedNetwork
    from tests.test_consensus import keys_for

    monkeypatch.setenv("LACHAIN_RBC_BATCH", "0")
    pub, privs = keys_for(4, 1)
    net = NativeSimulatedNetwork(pub, privs, use_rbc_batcher=True)
    try:
        assert net.rbc_batcher is None
    finally:
        net.close()


def test_device_failure_propagates(monkeypatch):
    """With the device path on, a device exception comes out of
    rs_batch._matmul (and the batcher above it): the numpy path is not a
    landing for exceptions, and nothing latches the device off."""
    monkeypatch.setenv("LACHAIN_RS_DEVICE", "1")

    def boom(*a, **k):
        raise RuntimeError("no device for you")

    monkeypatch.setattr(rs_batch, "_matmul_device", boom)
    data = bytes(range(256)) * 64  # big enough to cross _DEVICE_MIN_COLS
    with pytest.raises(RuntimeError, match="no device for you"):
        rs_batch.encode(data, 3, 7)
    batcher = RbcEraBatcher()
    batcher.submit_encode(1, data, 3, 7, lambda shards: None)
    with pytest.raises(RuntimeError, match="no device for you"):
        batcher.flush(1)
    # below the column floor the numpy path is the chosen path, not a fallback
    assert rs_batch.encode(b"small", 3, 7) == rs.encode(b"small", 3, 7)


# --- the device product: a GF(2) bit-plane matmul ----------------------------


def test_bit_matrix_is_multiplication_gf8():
    """For every a, x in GF(2^8): bit_matrix(a) times the bits of x, mod 2,
    is the bits of a*x."""
    import numpy as np

    gf = rs_batch.GF8
    a_bin = gf.bit_matrix(np.arange(256).reshape(256, 1))  # (8*256, 8)
    xs = np.arange(256)
    x_bits = (xs[None, :] >> np.arange(8)[:, None]) & 1  # (8, 256)
    prod = (a_bin.astype(np.int64) @ x_bits) & 1  # (o*256 + a, x)
    got = (prod.reshape(8, 256, 256) << np.arange(8)[:, None, None]).sum(0)
    want = [[gf.mul(a, x) for x in range(256)] for a in range(256)]
    assert got.tolist() == want


def _device_case(name):
    """(field, a, c): a matrix and a column count for the differential."""
    import zlib

    import numpy as np

    gf8, gf16 = rs_batch.GF8, rs_batch.gf16()
    rng = np.random.default_rng(zlib.crc32(name.encode()))

    def rand(field, r, k):
        return rng.integers(0, 1 << field.bits, (r, k)).astype(field.dtype)

    if name == "gf8_64x22":
        return gf8, rand(gf8, 64, 22), 5003
    if name == "gf8_22x22":
        return gf8, rand(gf8, 22, 22), 4097
    if name == "gf16_300x90":
        return gf16, rand(gf16, 300, 90), 777
    if name == "gf8_zeros":
        return gf8, np.zeros((64, 22), np.uint8), 1000
    if name == "gf8_all_ff":
        return gf8, np.full((64, 22), 0xFF, np.uint8), 1000
    if name == "gf16_all_ffff":
        return gf16, np.full((20, 7), 0xFFFF, np.uint16), 1000
    if name == "gf8_identity":
        return gf8, np.eye(22, dtype=np.uint8), 1000
    if name == "gf8_vandermonde":
        return gf8, rs_batch.vandermonde(gf8, 22, 64), 4500
    if name == "gf8_decode_inverse":
        xs = tuple(range(22, 44))
        return gf8, rs_batch._inverse_for(gf8, 22, xs), 4500
    if name == "gf16_vandermonde_512":
        return gf16, rs_batch.vandermonde(gf16, 172, 512), 300
    raise KeyError(name)


@pytest.mark.parametrize("devices", [8, 1])
@pytest.mark.parametrize(
    "name",
    [
        "gf8_64x22",
        "gf8_22x22",
        "gf16_300x90",
        "gf8_zeros",
        "gf8_all_ff",
        "gf16_all_ffff",
        "gf8_identity",
        "gf8_vandermonde",
        "gf8_decode_inverse",
        "gf16_vandermonde_512",
    ],
)
def test_device_product_equals_numpy(name, devices, monkeypatch):
    """_matmul_device (bit planes, one matmul, mod 2, packed) equals the
    numpy GF.matmul bit for bit, on the 8-device sharded path the test
    platform forces and on one device."""
    import jax
    import numpy as np

    assert jax.device_count() == 8
    if devices == 1:
        monkeypatch.setattr(jax, "device_count", lambda: 1)
    field, a, c = _device_case(name)
    rng = np.random.default_rng(c)
    b = rng.integers(0, 1 << field.bits, (a.shape[1], c)).astype(field.dtype)
    b[:, :3] = 0  # zero columns beside random ones
    b[:, 3] = field.order  # an all-ones column
    got = rs_batch._matmul_device(field, a, b, ("test", name))
    assert got.dtype == field.dtype and got.shape == (a.shape[0], c)
    assert np.array_equal(got, field.matmul(a, b))


def _device_counts():
    from lachain_tpu.utils import metrics

    return tuple(
        metrics.counter_value("rs_matmul_total", labels={"path": p})
        for p in ("device", "host")
    )


def test_new_matrix_same_shape_compiles_nothing(monkeypatch):
    """The bit matrix is an argument, not a traced constant: another
    payload and another erasure pattern of the same shapes reuse the one
    compiled program; each product counts once in rs_matmul_total."""
    monkeypatch.setenv("LACHAIN_RS_DEVICE", "1")
    n, k = 7, 3
    rng = random.Random(11)

    def round_trip(lost):
        items = [(rng.randbytes(7000), k, n) for _ in range(2)]  # 4668 cols
        enc = rs_batch.encode_batch(items)
        holes = [
            [None if i in lost else s for i, s in enumerate(shards)]
            for shards in enc
        ]
        assert rs_batch.decode_batch([(h, k) for h in holes]) == [
            d for d, _k, _n in items
        ]
        assert enc == [rs.encode(d, k, n) for d, _k, _n in items]

    before = _device_counts()
    round_trip({0, 1})
    assert _device_counts() == (before[0] + 2, before[1])
    compiled = rs_batch._device_jit()._cache_size()
    round_trip({2, 5, 6})  # another decode matrix, the same shapes
    assert rs_batch._device_jit()._cache_size() == compiled
    assert _device_counts() == (before[0] + 4, before[1])
    # under the column floor: the host path, once a product
    rs_batch.encode_batch([(b"small", 3, 7), (b"other", 2, 4)])
    assert _device_counts() == (before[0] + 4, before[1] + 2)


# --- end-to-end: block-hash identity on vs off, both engines -----------------


def _devnet_hashes(engine, rbc_batch, eras=2):
    from lachain_tpu.core.devnet import Devnet

    net = Devnet(
        4,
        1,
        initial_balances={bytes([9]) * 20: 10**9},
        seed=7,
        txs_per_block=8,
        engine=engine,
        rbc_batch=rbc_batch,
    )
    return [b.hash() for b in net.run_eras(1, eras)]


@pytest.mark.parametrize("engine", ["python", "native"])
def test_devnet_block_hash_identity_on_vs_off(engine):
    assert _devnet_hashes(engine, True) == _devnet_hashes(engine, False)


def test_devnet_batcher_actually_ran():
    from lachain_tpu.utils import metrics

    before = metrics.counter_value("rbc_flush_total") or 0.0
    _devnet_hashes("native", True)
    assert (metrics.counter_value("rbc_flush_total") or 0.0) > before


def test_forced_fallback_devnet_env(monkeypatch):
    """LACHAIN_RBC_BATCH=0 forces the per-message path even when the devnet
    asked for batching — hashes still match the batched run."""
    want = _devnet_hashes("native", True)
    monkeypatch.setenv("LACHAIN_RBC_BATCH", "0")
    assert _devnet_hashes("native", True) == want
