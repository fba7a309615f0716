#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path reaches the chip.

Run from the repo root with nothing set:  python3 chip_smoke.py

ONE process (it never sets JAX_PLATFORMS or XLA_FLAGS and starts no child
that needs jax). It fails unless jax found a TPU, and then:

  kernels   each device program against its plain host reference at the
            deployment's shapes — (a) the TPKE era batch, 64 slots x 64
            shares, through the backend's own pipeline vs HostEraPipeline,
            and a corrupted share isolates exactly its slot; (b) the coin
            era batch, 64 signers; (c) batched Reed-Solomon at (K, N) =
            (22, 64) vs scalar ops/rs.py; (d) 2048 ECDSA recoveries vs the
            native library; (e) the 22-point G2 MSM the coin combine uses.
  warm-up   warmup_era_kernels(64) to completion.
  devnet    BASELINE.json config 4 with the upstream block defaults: N=64,
            f=21, 1000-tx blocks, native engine, batched RBC, the TPU
            backend installed through provider.set_backend with every era
            batch routed to the device. Three eras of 1000 signed transfers
            from 256 funded accounts, made from the seed. Every era batch
            on the device, none on the host, no slot rejected, an rs.device
            span per era, no compilation after warm-up.
  reference the same Devnet, seed and transactions on the native host
            backend: block hashes and state roots equal era by era.

The last line of stdout is one JSON object with the device as jax reports
it. Any failure exits non-zero and prints no result. Times printed on the
way are plain facts about this run, labelled with the device — not metrics.
"""
from __future__ import annotations

import json
import os
import random
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N, F, TXS, USERS, SEED = 64, 21, 1000, 256, 7
ERAS = 3
TIME_LIMIT_S = 1200  # the contract's; compilation included
T0 = time.monotonic()


def elapsed() -> float:
    return time.monotonic() - T0


def say(msg: str) -> None:
    print(f"[chip_smoke +{elapsed():6.1f}s] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    say(("ok    " if ok else "FAIL  ") + what)
    if not ok:
        sys.exit(f"chip_smoke: FAILED: {what}")


class SeededRng:
    def __init__(self, seed: int):
        self._r = random.Random(seed)

    def randbelow(self, n: int) -> int:
        return self._r.randrange(n)


def counter(name: str, **labels) -> float:
    from lachain_tpu.utils import metrics

    return metrics.counter_value(name, labels=labels or None)


def rs_device_spans() -> list:
    from lachain_tpu.utils import tracing

    return [s for s in tracing.snapshot() if s["name"] == "rs.device"]


def peak_bytes() -> list:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()
    ]
    say(f"peak_bytes_in_use per device: {peaks}")
    return peaks


# -- phase 0: the device --------------------------------------------------------


def open_device() -> dict:
    from lachain_tpu.crypto import provider

    provider.open_device()  # imports jax, places the compile cache
    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    say(
        f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_version}"
    )
    say(f"compile cache directory: {provider.compile_cache_dir()}")
    check(
        device["platform"] == "tpu",
        f"jax.devices()[0].platform == 'tpu' (got {device['platform']!r})",
    )
    return device


def host_backend():
    """The native C++ host backend, built on this machine from the
    committed sources (utils/native_build.py keys the build on the CPU)."""
    from lachain_tpu.consensus import native_rt
    from lachain_tpu.crypto.native_backend import NativeBackend

    host = NativeBackend()
    say(
        f"host backend: {host.name}; ADX/BMI2 multiplier compiled in and "
        f"self-checked: {bool(host._lib.lt_have_adx())}"
    )
    check(host.name == "native", "host backend is native, not python")
    native_rt.load_rt()
    say("consensus engine: native (libconsensus_rt built and loaded)")
    return host


# -- phase 1: kernels against their plain references ---------------------------


def check_tpke(backend, host) -> None:
    """(a) 64 real slots x 64 real shares: device pipeline == host
    pipeline slot by slot; one corrupted share isolates its slot."""
    from lachain_tpu.crypto import bls12381 as bls
    from lachain_tpu.crypto import tpke
    from lachain_tpu.crypto.tpu_backend import EraSlotJob, TpuBackend
    from lachain_tpu.ops.verify import HostEraPipeline

    dealer = tpke.TpkeTrustedKeyGen(N, F, rng=SeededRng(SEED))
    lag_ids = list(range(F + 1))
    cs = bls.fr_lagrange_coeffs([i + 1 for i in lag_ids], at=0)
    lag_row = [0] * N
    for i, c in zip(lag_ids, cs):
        lag_row[i] = c
    jobs, msgs, cts = [], [], []
    for s in range(N):
        msg = bytes([s + 1]) * 32
        ct = dealer.pub.encrypt(msg, share_id=s, rng=SeededRng(SEED + s))
        u_row = [
            dealer.private_key(i).decrypt_share(ct, check=False).ui
            for i in range(N)
        ]
        jobs.append(
            EraSlotJob(
                u_by_validator=u_row,
                lagrange_row=list(lag_row),
                h=tpke.ciphertext_h(ct),
                w=ct.w,
            )
        )
        msgs.append(msg)
        cts.append(ct)
    vks = dealer.verification_keys
    reference = TpuBackend(
        host_backend=host, pipeline=HostEraPipeline(host), min_device_lanes=1
    )
    t = time.monotonic()
    got = backend.tpke_era_verify_combine(jobs, vks, rng=SeededRng(11))
    say(
        f"  first S={N} x K={N} era batch on the device: "
        f"{time.monotonic() - t:.1f} s (trace + compile + run)"
    )
    want = reference.tpke_era_verify_combine(jobs, vks, rng=SeededRng(11))
    same = all(
        g[0] and w[0] and bls.g1_eq(g[1], w[1]) for g, w in zip(got, want)
    )
    check(
        same, f"(a) tpke_era_verify_combine {N}x{N}: device == host, all ok"
    )
    plain = all(
        tpke.decrypt_with_combined(ct, g[1]) == m
        for ct, g, m in zip(cts, got, msgs)
    )
    check(plain, "(a) every slot decrypts to its plaintext")
    bad_slot, bad_share = N // 4 + 1, F // 4
    row = list(jobs[bad_slot].u_by_validator)
    row[bad_share] = bls.g1_mul(row[bad_share], 1337)
    poisoned = list(jobs)
    poisoned[bad_slot] = EraSlotJob(
        u_by_validator=row,
        lagrange_row=jobs[bad_slot].lagrange_row,
        h=jobs[bad_slot].h,
        w=jobs[bad_slot].w,
    )
    before = counter("crypto_tpu_era_slots_rejected_total")
    out = backend.tpke_era_verify_combine(poisoned, vks, rng=SeededRng(12))
    rejected = [i for i, (ok, _c) in enumerate(out) if not ok]
    check(
        rejected == [bad_slot]
        and counter("crypto_tpu_era_slots_rejected_total") == before + 1,
        f"(a) one corrupted share: exactly slot {bad_slot} rejected "
        f"(got {rejected})",
    )


def check_coins(backend, host) -> None:
    """(b) coin era batch, 64 signers, a few coins."""
    from lachain_tpu.crypto import bls12381 as bls
    from lachain_tpu.crypto import threshold_sig as ts
    from lachain_tpu.crypto.tpu_backend import CoinJob, TpuBackend
    from lachain_tpu.ops.verify import TsHostEraPipeline

    kg = ts.TsTrustedKeyGen(N, F, rng=SeededRng(SEED + 1))
    keys = kg.pub_key_set.keys
    signers = list(range(F + 1))
    cs = bls.fr_lagrange_coeffs([i + 1 for i in signers], at=0)
    jobs = []
    for c in range(4):
        msg = b"coin-%d" % c
        lag_row, sigma_row = [0] * N, [None] * N
        for i, coeff in zip(signers, cs):
            lag_row[i] = coeff
            sigma_row[i] = kg.private_key_share(i).sign(msg).sigma
        jobs.append(
            CoinJob(sigma_row, lag_row, ts._hash_to_sig_point(msg))
        )
    reference = TpuBackend(
        host_backend=host,
        ts_pipeline=TsHostEraPipeline(host),
        min_device_lanes=1,
    )
    got = backend.ts_era_verify_combine(jobs, keys, rng=SeededRng(21))
    want = reference.ts_era_verify_combine(jobs, keys, rng=SeededRng(21))
    same = all(
        g[0] and w[0] and bls.g2_eq(g[1], w[1]) for g, w in zip(got, want)
    )
    check(same, f"(b) ts_era_verify_combine 4 coins x {N}: device == host")
    sig = ts.Signature(got[0][1])
    check(
        kg.pub_key_set.shared.verify(b"coin-0", sig),
        "(b) combined signature verifies under the shared key",
    )
    bad = list(jobs[2].sigma_by_signer)
    bad[F // 4] = bls.g2_mul(bad[F // 4], 1337)
    poisoned = list(jobs)
    poisoned[2] = CoinJob(bad, jobs[2].lagrange_row, jobs[2].h)
    out = backend.ts_era_verify_combine(poisoned, keys, rng=SeededRng(22))
    check(
        [i for i, (ok, _s) in enumerate(out) if not ok] == [2],
        "(b) one corrupted share: exactly coin 2 rejected",
    )
    # (e) the coin combine the devnet leg runs: a 22-point G2 MSM
    pts = [j for j in jobs[0].sigma_by_signer if j is not None]
    check(
        bls.g2_eq(backend.g2_msm(pts, cs), host.g2_msm(pts, cs))
        and backend.device_msm_calls > 0,
        f"(e) g2_msm of f+1={len(pts)} points: device == host",
    )


def check_rs() -> None:
    """(c) batched RS at (22, 64), enough columns for the device program,
    at every column padding a 1000-tx era can reach."""
    from lachain_tpu.ops import rs, rs_batch

    k = N - 2 * F
    rnd = random.Random(SEED)
    for payload in (1500, 3000, 6000, 12000):
        before = len(rs_device_spans())
        items = [(rnd.randbytes(payload), k, N) for _ in range(N)]
        enc = rs_batch.encode_batch(items)
        want = [rs.encode(d, k, N) for d, _k, _n in items]
        holes = [
            [None] * F + shards[F : F + k] + [None] * (N - F - k)
            for shards in enc
        ]
        dec = rs_batch.decode_batch([(h, k) for h in holes])
        spans = rs_device_spans()[before:]
        cols = sorted({s["args"]["cols"] for s in spans})
        check(
            enc == want
            and dec == [d for d, _k, _n in items]
            and all(rs.decode(h, k) == d for h, (d, _k, _n) in zip(holes, items))
            and len(spans) == 2
            and cols[0] >= rs_batch._DEVICE_MIN_COLS,
            f"(c) rs encode/decode ({k},{N}) x {N} payloads of {payload} B: "
            f"bit-identical to ops/rs.py, on the device at {cols} columns",
        )


def check_ecdsa(host) -> None:
    """(d) 2048 recoveries: ops/psecp.py vs the native library."""
    from lachain_tpu.crypto import ecdsa
    from lachain_tpu.ops.psecp import TpuEcdsaRecover

    rnd = random.Random(SEED)
    privs = [ecdsa.generate_private_key(SeededRng(100 + i)) for i in range(32)]
    hashes = [rnd.randbytes(32) for _ in range(2048)]
    sigs = [ecdsa.sign_hash(privs[i % 32], h) for i, h in enumerate(hashes)]
    broken = bytearray(sigs[7])
    broken[40] ^= 0xFF
    sigs[7] = bytes(broken)
    # adversarial u1*R == u2*G (R = kG, s = (n - z)/k): the kernel's
    # incomplete pairwise add degenerates and the host must answer
    k, z = 0x1234567, 0x55AA
    big_r = ecdsa._mul(ecdsa.G, k)
    s_val = (ecdsa.N - z) * pow(k, -1, ecdsa.N) % ecdsa.N
    hashes[9] = z.to_bytes(32, "big")
    sigs[9] = (
        big_r[0].to_bytes(32, "big")
        + s_val.to_bytes(32, "big")
        + bytes([big_r[1] & 1])
    )
    got = TpuEcdsaRecover().recover_batch(hashes, sigs)
    # two halves: each stays under the size that routes to the chip
    want = ecdsa.recover_hash_batch(
        hashes[:1024], sigs[:1024]
    ) + ecdsa.recover_hash_batch(hashes[1024:], sigs[1024:])
    check(
        got == want and sum(w is not None for w in want) >= 2047,
        "(d) 2048-signature TpuEcdsaRecover == native lt_ec_recover_batch",
    )


# -- phases 2-4: warm-up, the devnet on the device, the host reference ---------


def run_devnet(eras: int, on_device: bool) -> list:
    """Three eras of seeded signed transfers; returns per
    era (block hash, state root, tx count, wall seconds)."""
    from lachain_tpu.core.devnet import Devnet
    from lachain_tpu.core.types import Transaction, sign_transaction
    from lachain_tpu.crypto import ecdsa

    users = [ecdsa.generate_private_key(SeededRng(5 + i)) for i in range(USERS)]
    balances = {
        ecdsa.address_from_public_key(ecdsa.public_key_bytes(u)): 10**24
        for u in users
    }
    net = Devnet(
        N,
        F,
        initial_balances=balances,
        seed=SEED,
        txs_per_block=TXS,
        engine="native",
        rbc_batch=True,
    )
    nonces = [0] * USERS
    out = []
    try:
        for era in range(1, eras + 1):
            for k in range(TXS):
                u = k % USERS
                stx = sign_transaction(
                    Transaction(
                        to=bytes([era % 256]) * 20,
                        value=1,
                        nonce=nonces[u],
                        gas_price=1 + (k % 7),
                        gas_limit=21000,
                    ),
                    users[u],
                    net.chain_id,
                )
                net.submit_tx(stx)
                nonces[u] += 1
            marks = {
                "device": counter("crypto_tpu_era_route_total", path="device"),
                "host": counter("crypto_tpu_era_route_total", path="host"),
                "rejected": counter("crypto_tpu_era_slots_rejected_total"),
                "compiles": counter("device_compile_requests_total"),
                "rs": len(rs_device_spans()),
            }
            t = time.monotonic()
            block = net.run_era(era, max_messages=20_000_000)[0]
            wall = time.monotonic() - t
            out.append(
                (block.hash(), block.header.state_hash, len(block.tx_hashes))
            )
            say(
                f"  era {era}: {len(block.tx_hashes)} txs, block "
                f"{block.hash().hex()[:16]}, state root "
                f"{block.header.state_hash.hex()[:16]}, {wall:.1f} s wall"
            )
            if not on_device:
                continue
            device = counter("crypto_tpu_era_route_total", path="device")
            rs_spans = rs_device_spans()[marks["rs"] :]
            say(
                f"    era batches on the device: "
                f"{device - marks['device']:.0f}; rs.device spans: "
                f"{len(rs_spans)}, largest "
                f"{max((s['args']['cols'] for s in rs_spans), default=0)} "
                f"columns"
            )
            check(device > marks["device"], f"era {era}: era batch on the device")
            check(
                counter("crypto_tpu_era_route_total", path="host")
                == marks["host"],
                f"era {era}: no era batch on the host route",
            )
            check(
                counter("crypto_tpu_era_slots_rejected_total")
                == marks["rejected"],
                f"era {era}: no slot rejected by the device",
            )
            check(len(rs_spans) >= 1, f"era {era}: an rs.device span")
            check(
                counter("device_compile_requests_total") == marks["compiles"],
                f"era {era}: no compilation after warm-up",
            )
    finally:
        net.close()
    return out


def main() -> None:
    signal.alarm(TIME_LIMIT_S - 30)  # a hang is a failure, not a timeout
    device = open_device()
    host = host_backend()

    from lachain_tpu.crypto import provider
    from lachain_tpu.crypto.tpu_backend import TpuBackend
    from lachain_tpu.crypto.warmup import warmup_era_kernels

    backend = TpuBackend(host_backend=host, min_device_lanes=1)
    provider.set_backend(backend)
    say(
        f"era pipeline: {type(backend._get_pipeline()).__name__}; coin "
        f"pipeline: {type(backend._get_ts_pipeline()).__name__}"
    )
    label = f"{device['kind']} x{device['count']}"

    check_tpke(backend, host)
    check_coins(backend, host)
    check_rs()
    check_ecdsa(host)
    peak_bytes()

    t = time.monotonic()
    warmup_era_kernels(N, backend=backend).join()
    requests = counter("device_compile_requests_total")
    hits = counter("device_compile_cache_hits_total")
    say(
        f"warm-up done on {label}: warmup_era_kernels({N}) took "
        f"{time.monotonic() - t:.1f} s; {elapsed():.1f} s since start; "
        f"{requests:.0f} programs built so far, {hits:.0f} from the compile "
        f"cache, {requests - hits:.0f} cold compiles"
    )

    eras = ERAS
    # the two devnet legs take about a minute together on a v5e host; 420 s
    # leaves that several times over on a slower one
    if elapsed() > TIME_LIMIT_S - 420:
        eras = 2
        say(
            f"REDUCTION: {eras} eras instead of {ERAS} — {elapsed():.0f} s "
            f"of the {TIME_LIMIT_S} s limit went to cold compiles "
            f"(N, f and the block size are not cut)"
        )
    say(f"devnet on the device ({label}): N={N} f={F} {TXS}-tx blocks")
    on_device = run_devnet(eras, on_device=True)
    check(all(p > 0 for p in peak_bytes()), "memory was in use on every device")

    say("same devnet on the native host backend (the plain reference)")
    provider.set_backend(host)
    on_host = run_devnet(eras, on_device=False)
    for era, (dev_era, host_era) in enumerate(zip(on_device, on_host), 1):
        check(
            dev_era == host_era,
            f"era {era}: block hash and state root equal on device and host",
        )
    say(f"all checks passed in {elapsed():.1f} s on {label}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
