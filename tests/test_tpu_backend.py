"""The TPU backend behind the provider seam (VERDICT r2 item #2).

Covers:
  * `LACHAIN_TPU_BACKEND=tpu` resolution through get_backend()
  * era-shaped batch verify+combine vs the host oracle, including slots with
    missing shares (masked lanes) and non-power-of-two slot counts
  * byzantine share isolation: the grand check fails, bisection reports the
    poisoned slot, valid slots still decrypt
  * the LIVE consensus path: a HoneyBadger simulation with the tpu backend
    installed must route decryption through the era kernel (era_calls > 0)
    and produce the same results as the host backends.

Reference semantics being accelerated: TPKE/PublicKey.cs:55-92 via
HoneyBadger.cs:205-247 (serial 2-pairings-per-share there; one kernel launch
plus one grand multi-pairing here).
"""
import random

import pytest

from lachain_tpu.crypto import bls12381 as bls
from lachain_tpu.crypto import tpke
from lachain_tpu.crypto.provider import get_backend, set_backend
from lachain_tpu.crypto.tpu_backend import EraSlotJob, TpuBackend


class SeededRng:
    def __init__(self, seed):
        self._r = random.Random(seed)

    def randbelow(self, n):
        return self._r.randrange(n)


@pytest.fixture
def tpu_backend():
    prev = get_backend()
    backend = TpuBackend(host_backend=prev)
    set_backend(backend)
    try:
        yield backend
    finally:
        set_backend(prev)


def _make_era(n, f, n_slots, seed=7):
    dealer = tpke.TpkeTrustedKeyGen(n, f, rng=SeededRng(seed))
    slots = []
    for s in range(n_slots):
        msg = bytes([s + 1]) * 32
        ct = dealer.pub.encrypt(msg, share_id=s, rng=SeededRng(seed + s))
        decs = [
            dealer.private_key(i).decrypt_share(ct, check=False)
            for i in range(n)
        ]
        slots.append((ct, decs, msg))
    return dealer, slots


def _job_for(n, f, ct, decs_by_id):
    """Build an EraSlotJob from a {validator: share} dict (live-node shape)."""
    chosen = sorted(decs_by_id)[: f + 1]
    cs = bls.fr_lagrange_coeffs([i + 1 for i in chosen], at=0)
    lag = [0] * n
    for i, c in zip(chosen, cs):
        lag[i] = c
    u_row = [decs_by_id[i].ui if i in decs_by_id else None for i in range(n)]
    return EraSlotJob(
        u_by_validator=u_row,
        lagrange_row=lag,
        h=tpke._hash_uv_to_g2(ct.u, ct.v),
        w=ct.w,
    )


def test_env_var_resolves_tpu_backend(monkeypatch):
    import lachain_tpu.crypto.provider as provider

    monkeypatch.setenv("LACHAIN_TPU_BACKEND", "tpu")
    monkeypatch.setattr(provider, "_BACKEND", None)
    backend = provider.get_backend()
    assert backend.name == "tpu"
    assert hasattr(backend, "tpke_era_verify_combine")
    # delegated host ops still work through the seam
    assert backend.hash_to_g2(b"x") is not None
    provider._BACKEND = None  # do not leak into other tests


def test_era_verify_combine_full_and_partial_slots(tpu_backend):
    n, f = 5, 1  # non-power-of-two K exercises lane padding
    dealer, slots = _make_era(n, f, n_slots=3)
    jobs = []
    # slot 0: all N shares; slot 1: only F+1 shares (masked lanes);
    # slot 2: an arbitrary F+2 subset -> 3 slots pads to S_pad=4
    subsets = [list(range(n)), [1, 3], [0, 2, 4]]
    for (ct, decs, _), subset in zip(slots, subsets):
        jobs.append(_job_for(n, f, ct, {i: decs[i] for i in subset}))
    out = tpu_backend.tpke_era_verify_combine(
        jobs, dealer.verification_keys, rng=SeededRng(99)
    )
    assert tpu_backend.era_calls == 1
    assert len(out) == 3
    for (ct, _, msg), (ok, combined) in zip(slots, out):
        assert ok
        pad = tpke._pad(combined, len(ct.v))
        assert bytes(a ^ b for a, b in zip(ct.v, pad)) == msg


def test_era_verify_combine_isolates_poisoned_slot(tpu_backend):
    n, f = 4, 1
    dealer, slots = _make_era(n, f, n_slots=2, seed=21)
    jobs = []
    for s, (ct, decs, _) in enumerate(slots):
        by_id = {i: decs[i] for i in range(n)}
        if s == 1:  # corrupt one share in slot 1
            bad = tpke.PartiallyDecryptedShare(
                ui=bls.g1_mul(bls.G1_GEN, 1234567),
                decryptor_id=2,
                share_id=by_id[2].share_id,
            )
            by_id[2] = bad
        jobs.append(_job_for(n, f, ct, by_id))
    out = tpu_backend.tpke_era_verify_combine(
        jobs, dealer.verification_keys, rng=SeededRng(5)
    )
    ok0, combined0 = out[0]
    ok1, combined1 = out[1]
    assert ok0 and combined0 is not None
    assert not ok1 and combined1 is None
    ct0, _, msg0 = slots[0]
    pad = tpke._pad(combined0, len(ct0.v))
    assert bytes(a ^ b for a, b in zip(ct0.v, pad)) == msg0


def test_ts_era_verify_combine(tpu_backend):
    """Coin-era batch: full and partial coins verify+combine correctly and
    a poisoned coin is isolated while the others still produce combined
    signatures that validate against the shared key."""
    from lachain_tpu.crypto import threshold_sig as ts

    n, f = 4, 1
    dealer = ts.TsTrustedKeyGen(n, f, rng=SeededRng(31))
    ks = dealer.pub_key_set
    msgs = [b"coin|%d" % i for i in range(3)]
    coins = []
    for m in msgs:
        shares = {
            i: dealer.private_key_share(i).sign(m) for i in range(n)
        }
        coins.append((m, shares))
    # partial coin: only t+1 shares present
    del coins[1][1][0], coins[1][1][3]
    sigs = ts.era_verify_combine(ks, coins, rng=SeededRng(77))
    assert tpu_backend.ts_era_calls == 1
    assert tpu_backend.ts_era_coins_total == 3
    for m, sig in zip(msgs, sigs):
        assert sig is not None
        assert ks.shared.verify(m, sig)
    # poison one share of coin 0
    bad = ts.PartialSignature(
        sigma=bls.g2_mul(bls.G2_GEN, 4242), signer_id=1
    )
    coins[0][1][1] = bad
    sigs2 = ts.era_verify_combine(ks, coins, rng=SeededRng(78))
    assert sigs2[0] is None  # isolated
    assert sigs2[1] is not None and sigs2[2] is not None
    assert ks.shared.verify(msgs[2], sigs2[2])


def test_honey_badger_sim_routes_through_tpu(tpu_backend):
    """End-to-end: the consensus hot path executes on the device kernel with
    LACHAIN_TPU_BACKEND=tpu semantics (backend installed via the seam)."""
    from lachain_tpu.consensus import messages as M
    from lachain_tpu.consensus.keys import trusted_key_gen
    from lachain_tpu.consensus.simulator import DeliveryMode, SimulatedNetwork

    n, f = 4, 1
    pub, privs = trusted_key_gen(n, f, rng=SeededRng(1001))
    net = SimulatedNetwork(pub, privs, seed=3, mode=DeliveryMode.TAKE_RANDOM)
    pid = M.HoneyBadgerId(era=0)
    for i in range(n):
        net.post_request(i, pid, b"txbatch|%d|" % i + bytes(32))

    def done():
        return all(r.result_of(pid) is not None for r in net.routers)

    assert net.run(done)
    results = net.results(pid)
    assert all(r == results[0] for r in results)
    assert len(results[0]) >= n - f
    # the device path actually executed (not the host fallback)
    assert tpu_backend.era_calls > 0
    assert tpu_backend.era_slots_total >= n - f


def test_era_batch_records_pad_waste_and_route_metrics(tpu_backend):
    """The era batch records the observability trio the ISSUE names: the
    raw/padded slot counts, the pad-waste fraction, and which pipeline
    (device vs host) the call routed to."""
    from lachain_tpu.utils import metrics

    metrics.reset_all_for_tests()
    n, f = 4, 1
    dealer, slots = _make_era(n, f, n_slots=3, seed=13)
    jobs = [
        _job_for(n, f, ct, {i: decs[i] for i in range(n)})
        for (ct, decs, _) in slots
    ]
    out = tpu_backend.tpke_era_verify_combine(
        jobs, dealer.verification_keys, rng=SeededRng(42)
    )
    assert all(ok for ok, _ in out)
    # 3 slots pad to S_pad=4: one dummy slot, waste 0.25
    assert metrics.counter_value("crypto_tpu_era_slots_padded_total") == 1
    assert (
        metrics.counter_value("crypto_tpu_era_route_total", labels={"path": "host"})
        == 1
    )
    waste = metrics.histogram_snapshot("crypto_tpu_era_pad_waste")
    assert waste["count"] == 1
    assert abs(waste["sum"] - 0.25) < 1e-9
    sizes = metrics.histogram_snapshot("crypto_tpu_era_batch_slots")
    assert sizes["count"] == 1 and sizes["sum"] == 3
    lat = metrics.histogram_snapshot(
        "crypto_tpu_era_pipeline_seconds", labels={"path": "host"}
    )
    assert lat["count"] == 1 and lat["sum"] > 0


def test_adaptive_device_msm_routing(tpu_backend, monkeypatch):
    """g1_msm/g2_msm route big batches to the device path and small ones
    to the host. The device kernel math is covered by test_pg1/test_pg2
    (and validated on-chip); here _device_msm is stubbed so the routing
    decision itself is cheap to test on CPU."""
    import random as _random

    monkeypatch.setenv("LTPU_FORCE_PALLAS", "1")
    calls = []
    real_host = tpu_backend._host

    def fake_device_msm(points, scalars, g2):
        calls.append(g2)
        fn = real_host.g2_msm if g2 else real_host.g1_msm
        return fn(points, scalars)

    monkeypatch.setattr(tpu_backend, "_device_msm", fake_device_msm)
    tpu_backend.min_device_lanes = 4
    r = _random.Random(5)
    pts1 = [bls.g1_mul(bls.G1_GEN, r.randrange(1, bls.R)) for _ in range(5)]
    pts2 = [bls.g2_mul(bls.G2_GEN, r.randrange(1, bls.R)) for _ in range(5)]
    ss = [r.randrange(1, bls.R) for _ in range(5)]
    got1 = tpu_backend.g1_msm(pts1, ss)
    got2 = tpu_backend.g2_msm(pts2, ss)
    assert bls.g1_eq(got1, real_host.g1_msm(pts1, ss))
    assert bls.g2_eq(got2, real_host.g2_msm(pts2, ss))
    assert calls == [False, True]
    # below threshold -> host, no device call
    tpu_backend.min_device_lanes = 64
    tpu_backend.g1_msm(pts1, ss)
    assert calls == [False, True]

# slice marker: crypto/accelerator kernels ("make test-kernel")
pytestmark = pytest.mark.kernel
