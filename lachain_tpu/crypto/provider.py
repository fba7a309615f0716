"""Crypto backend provider seam.

Parity with the reference's provider seam (`ICrypto` / `CryptoProvider`,
/root/reference/src/Lachain.Crypto/CryptoProvider.cs:3-11 and ICrypto.cs:5-117):
all threshold-crypto consumers go through a small backend interface so the
implementation can be swapped without touching consensus code.

Three backends exist:
  * ``python``  — the pure-Python oracle (lachain_tpu.crypto.bls12381).
  * ``native``  — C++ libbls381 via ctypes (fast host path; MCL equivalent).
  * ``tpu``     — Pallas era kernels for the MSM-heavy batch ops
                  (crypto/tpu_backend.py over ops/pg1.py); pairings,
                  hashing and scalar ops delegate to native/python.

The batch operations are the TPU-first redesign: where the reference verifies
each decryption share with 2 pairings (TPKE/PublicKey.cs:88-92, executed
serially per message), we reduce a whole batch to ONE pairing equality via a
random-linear-combination MSM, so the hot op becomes a batched G1/G2 MSM —
exactly the shape TPUs are good at.
"""
from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

from . import bls12381 as bls


class PythonBackend:
    """Oracle backend: direct calls into the pure-Python BLS12-381 module."""

    name = "python"

    # -- group ops -----------------------------------------------------------
    def g1_msm(self, points: Sequence[tuple], scalars: Sequence[int]) -> tuple:
        acc = bls.G1_INF
        for pt, s in zip(points, scalars):
            acc = bls.g1_add(acc, bls.g1_mul(pt, s))
        return acc

    def g2_msm(self, points: Sequence[tuple], scalars: Sequence[int]) -> tuple:
        acc = bls.G2_INF
        for pt, s in zip(points, scalars):
            acc = bls.g2_add(acc, bls.g2_mul(pt, s))
        return acc

    def g1_mul(self, point: tuple, scalar: int) -> tuple:
        return bls.g1_mul(point, scalar)

    def g2_mul(self, point: tuple, scalar: int) -> tuple:
        return bls.g2_mul(point, scalar)

    # -- pairings ------------------------------------------------------------
    def pairing_check(
        self, pairs: Sequence[Tuple[tuple, tuple]]
    ) -> bool:
        """Prod e(Pi, Qi) == 1 with one shared final exponentiation."""
        return bls.fp12_eq_one(bls.multi_pairing(pairs))

    def pairings_equal(self, p_a, q_a, p_b, q_b) -> bool:
        return bls.pairings_equal(p_a, q_a, p_b, q_b)

    # -- hashing -------------------------------------------------------------
    def hash_to_g1(self, msg: bytes, domain: bytes = b"LTPU-G1") -> tuple:
        return bls.hash_to_g1(msg, domain)

    def hash_to_g2(self, msg: bytes, domain: bytes = b"LTPU-G2") -> tuple:
        return bls.hash_to_g2(msg, domain)

    # -- wire deserialization (on-curve + subgroup validation) ---------------
    def g1_deserialize(self, data: bytes) -> tuple:
        return bls.g1_from_bytes(data, check_subgroup=True)

    def g2_deserialize(self, data: bytes) -> tuple:
        return bls.g2_from_bytes(data, check_subgroup=True)

    # -- era-shaped batch ops ------------------------------------------------
    def tpke_era_verify_combine(self, jobs, verification_keys, rng=None):
        """Whole-tick TPKE verify+combine (one grand multi-pairing); same
        contract as the TPU backend's kernel-backed version."""
        import secrets as _secrets

        from . import tpke

        return tpke.era_verify_combine_host(
            jobs, verification_keys, backend=self, rng=rng or _secrets
        )


def batch_bisect_verify(group_ok, n: int) -> List[bool]:
    """Shared bisection driver for random-linear-combination batch checks.

    `group_ok(idx_list) -> bool` must be a probabilistic check that a subset of
    items is all-valid (e.g. an RLC pairing equality). Returns per-item
    validity; cost is one group check when everything is valid, and
    O(log n) group checks per invalid item otherwise. Used by both TPKE
    decryption-share verification and threshold-signature share verification
    so the soundness-critical logic lives in exactly one place.
    """
    results = [False] * n

    def solve(idx):
        if group_ok(idx):
            for i in idx:
                results[i] = True
            return
        if len(idx) == 1:
            return
        mid = len(idx) // 2
        solve(idx[:mid])
        solve(idx[mid:])

    if n:
        solve(list(range(n)))
    return results


def deserialize_batch_g1(datas, backend=None, rng=None):
    """Parse many G1 encodings; invalid entries come back as None.

    Every point gets a SOUND per-point subgroup check (the backend's checked
    deserializer). An aggregate random-linear-combination check is NOT sound
    here: E(Fp)'s cofactor has small prime factors (3 and 11 for G1; 13/23
    for G2's twist), so a random weight annihilates an order-3 torsion
    component with probability 1/3 — and a rogue share surviving into a
    combination yields divergent plaintexts across honest validators. The
    batching wins that ARE safe (and used): parse lazily (only the t+1
    CHOSEN shares pay the check, not all N arrivals) and memoize by exact
    wire bytes (identical bytes validate once — in the in-process simulator
    all N validators receive the same broadcast bytes; a real node sees the
    same share via gossip redundancy and replays).
    """
    backend = backend or get_backend()
    return [_memo_parse(d, backend.g1_deserialize, _G1_MEMO) for d in datas]


def deserialize_batch_g2(datas, backend=None, rng=None):
    """G2 analogue of deserialize_batch_g1 (same per-point soundness)."""
    backend = backend or get_backend()
    return [_memo_parse(d, backend.g2_deserialize, _G2_MEMO) for d in datas]


# bytes -> validated point tuple (or None for invalid encodings; points are
# immutable tuples so sharing across callers is safe). Bounded: cleared
# wholesale at the cap — distinct entries per era are few thousand, so the
# cap is hit rarely and a cold restart only re-validates.
_G1_MEMO: dict = {}
_G2_MEMO: dict = {}
_MEMO_CAP = 1 << 18


def _memo_parse(data, parse, memo):
    hit = memo.get(data)
    if hit is not None or data in memo:
        return hit
    try:
        pt = parse(data)
    except (ValueError, AssertionError):
        pt = None
    if len(memo) >= _MEMO_CAP:
        memo.clear()
    memo[bytes(data)] = pt
    return pt


def select_distinct(shares, key, count: int):
    """First `count` shares with distinct `key(share)`, or None if impossible.

    Used before Lagrange combination: duplicates are skipped (not an error)
    so a caller holding [id0, id0, id1, id2] can still combine t+1 = 3
    distinct shares.
    """
    seen = set()
    out = []
    for s in shares:
        k = key(s)
        if k in seen:
            continue
        seen.add(k)
        out.append(s)
        if len(out) == count:
            return out
    return None


_BACKEND = None
_DEVICE_PLATFORM: list = []  # [platform] once this process has opened JAX

# <checkout>/.jax_cache: a fixed path derived from the package's own
# location (never a temp name, a pid or ~), git-ignored
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """Where compiled device programs persist: $JAX_COMPILATION_CACHE_DIR
    when set (jax reads it itself; nothing is set in code), else
    <checkout>/.jax_cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_COMPILE_CACHE


def open_device() -> str:
    """Import jax for this process, place its compile cache, and return the
    platform it resolved to. Called only on behalf of a device backend
    (TpuBackend); the ONE place that reads jax.default_backend().

    A device was asked for, so landing on the CPU is an error (chip held by
    another process, libtpu failing to initialise) unless JAX_PLATFORMS
    names exactly `cpu` — how the tests ask for the host emulation."""
    if _DEVICE_PLATFORM:
        return _DEVICE_PLATFORM[0]
    import jax

    platform = jax.default_backend()
    if platform == "cpu":
        # the emulation keeps no compile cache: XLA:CPU executables are
        # tied to the compiling machine's features, and a copied tree
        # would load them elsewhere
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
            raise RuntimeError(
                "the tpu backend was asked for but jax resolved to the CPU "
                "(chip held by another process, or libtpu failed to "
                "start); set JAX_PLATFORMS=cpu to run the host emulation "
                "on purpose"
            )
    else:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update(
                "jax_compilation_cache_dir", _DEFAULT_COMPILE_CACHE
            )
        # every program is worth keeping: the RS matmul compiles in well
        # under jax's default 1 s floor and would recompile on every start
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _count_compiles()
    _DEVICE_PLATFORM.append(platform)
    return platform


def _count_compiles() -> None:
    """Mirror jax's own compile log into /metrics: every program build
    (device_compile_requests_total) and how many of them the persistent
    cache answered (device_compile_cache_hits_total). Warm-up is over
    when the first stops moving; a cold start is requests minus hits."""
    from jax import monitoring

    from ..utils import metrics

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            metrics.inc("device_compile_requests_total")

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            metrics.inc("device_compile_cache_hits_total")

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def device_platform() -> Optional[str]:
    """Platform of the jax device this process owns, or None when the
    active backend is a host backend — in which case jax is never
    imported, so several node processes can share a host with one chip."""
    if getattr(get_backend(), "name", None) != "tpu":
        return None
    return open_device()


def get_backend():
    """Singleton accessor (role of CryptoProvider.GetCrypto in the reference).

    $LACHAIN_TPU_BACKEND picks python | native | tpu; unset means native.
    A native library that fails to build is an error, not the Python
    oracle; `tpu` on a process that landed on the CPU is an error too
    (open_device)."""
    global _BACKEND
    if _BACKEND is not None:
        return _BACKEND
    choice = os.environ.get("LACHAIN_TPU_BACKEND", "auto")
    if choice == "tpu":
        from .tpu_backend import TpuBackend

        open_device()
        _BACKEND = TpuBackend()
    elif choice == "python":
        _BACKEND = PythonBackend()
    elif choice in ("native", "auto"):
        from .native_backend import NativeBackend

        _BACKEND = NativeBackend()
    else:
        raise ValueError(f"unknown LACHAIN_TPU_BACKEND={choice!r}")
    return _BACKEND


def set_backend(backend) -> None:
    global _BACKEND
    _BACKEND = backend
