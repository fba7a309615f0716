"""Batched Reed-Solomon over GF(2^8)/GF(2^16): Vandermonde matrix form.

The scalar codec (ops/rs.py) walks one payload at a time: Horner evaluation
per shard on encode, a per-item Gauss-Jordan + row accumulation on decode.
Algebraically both are matrix products — encode is `V @ C` for the n x k
Vandermonde V (rows [x^0 .. x^{k-1}] at x = 1..n) against the k x L
coefficient matrix C, and decode is `inv(V_sel) @ R` for the received rows.
This module computes them that way, batched: all pending items that share a
(field, k, n) — or for decode a (field, k, erasure-pattern) — are
column-concatenated into ONE matrix product per group, which is the shape
"the designated second TPU kernel" (ops/rs.py docstring, PAPER.md §2a)
wants: a log/exp table gather plus an XOR reduction over the contraction
axis. When this process owns an accelerator (crypto/provider.py decides; or
LACHAIN_RS_DEVICE=1 forces it) products of at least _DEVICE_MIN_COLS
columns are jitted and dispatched to the device, sharded across the PR 14
mesh along the column (slot-payload) axis; otherwise the same gather + XOR
runs vectorized in numpy. Both paths use the identical exp/log tables,
so results are bit-identical to ops/rs.py (tests/test_rs_batch.py pins a
200-seed differential).

GF(2^16) (poly x^16+x^12+x^3+x+1 = 0x1100B, generator 2) backs shard counts
past GF(2^8)'s 255 evaluation points: symbols are big-endian uint16 pairs,
shard byte sizes are even, and an odd-sized shard is a clean decode failure.
This removes the n > 255 whole-payload replication fallback that capped
honest coding at N=255 (consensus_rt.cpp keeps replication as its
engine-internal fallback when no host shim is attached).
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import tracing

# device dispatch is worth its ferry cost only past a column threshold;
# below it the numpy path wins outright
_DEVICE_MIN_COLS = 4096


class GF:
    """A binary field GF(2^bits) with exp/log tables (generator 2)."""

    def __init__(self, bits: int, poly: int):
        self.bits = bits
        self.order = (1 << bits) - 1
        self.poly = poly
        self.dtype = np.uint8 if bits == 8 else np.uint16
        # big-endian wire dtype: shard bytes <-> symbol arrays
        self.be_dtype = np.uint8 if bits == 8 else np.dtype(">u2")
        self.sym_size = 1 if bits == 8 else 2
        exp = np.zeros(2 * self.order, dtype=self.dtype)
        log = np.zeros(1 << bits, dtype=np.int32)
        x = 1
        for i in range(self.order):
            exp[i] = x
            log[x] = i
            x <<= 1
            if x & (1 << bits):
                x ^= poly
        # generator 2 must cycle through every nonzero element exactly once
        assert x == 1, f"generator 2 is not primitive for poly {poly:#x}"
        exp[self.order :] = exp[: self.order]
        self.exp, self.log = exp, log

    def mul(self, a: int, b: int) -> int:
        if a == 0 or b == 0:
            return 0
        return int(self.exp[self.log[a] + self.log[b]])

    def inv(self, a: int) -> int:
        if a == 0:
            raise ZeroDivisionError("gf_inv(0)")
        return int(self.exp[self.order - self.log[a]])

    def matmul(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """GF matrix product a (r,k) @ b (k,c): exp[log+log] gather with
        zero masks, XOR-accumulated over the contraction axis. The j-loop
        bounds peak memory at one (r,c) plane per step."""
        a = np.ascontiguousarray(a, dtype=self.dtype)
        b = np.ascontiguousarray(b, dtype=self.dtype)
        r, k = a.shape
        c = b.shape[1]
        out = np.zeros((r, c), dtype=self.dtype)
        log_b = self.log[b]  # (k, c)
        mask_b = b != 0
        log_a = self.log[a]  # (r, k)
        mask_a = a != 0
        for j in range(k):
            if not mask_a[:, j].any() or not mask_b[j].any():
                continue
            prod = self.exp[log_a[:, j, None] + log_b[j][None, :]]
            np.bitwise_xor(
                out,
                np.where(mask_a[:, j, None] & mask_b[j][None, :], prod, 0),
                out=out,
            )
        return out

    def mat_inv(self, mat: np.ndarray) -> Optional[np.ndarray]:
        """Gauss-Jordan inversion (first-nonzero pivot, same scan order as
        ops/rs.py::_gf_mat_inv); None when singular."""
        k = mat.shape[0]
        a = mat.astype(np.int64).copy()
        inv = np.eye(k, dtype=np.int64)
        exp, log, order = self.exp, self.log, self.order
        for col in range(k):
            piv = None
            for r in range(col, k):
                if a[r, col] != 0:
                    piv = r
                    break
            if piv is None:
                return None
            if piv != col:
                a[[col, piv]] = a[[piv, col]]
                inv[[col, piv]] = inv[[piv, col]]
            pinv = self.inv(int(a[col, col]))
            for row_arr in (a, inv):
                row = row_arr[col]
                nz = row != 0
                row[nz] = exp[log[row[nz]] + log[pinv]]
            for r in range(k):
                if r == col or a[r, col] == 0:
                    continue
                fac = int(a[r, col])
                for row_arr in (a, inv):
                    prow = row_arr[col]
                    nz = prow != 0
                    term = np.zeros(k, dtype=np.int64)
                    term[nz] = exp[log[prow[nz]] + log[fac]]
                    row_arr[r] ^= term
        return inv.astype(self.dtype)


GF8 = GF(8, 0x11D)  # matches ops/rs.py tables exactly

_GF16_CACHE: List[Optional[GF]] = [None]


def gf16() -> GF:
    """GF(2^16) built on first use (the 65535-step table bootstrap is not
    free; n <= 255 workloads never pay it)."""
    if _GF16_CACHE[0] is None:
        _GF16_CACHE[0] = GF(16, 0x1100B)
    return _GF16_CACHE[0]


def field_for(n: int) -> GF:
    if n <= 255:
        return GF8
    if n <= 65535:
        return gf16()
    raise ValueError(f"n={n} exceeds GF(2^16) evaluation points")


# -- cached per-(field, k, n) matrices ---------------------------------------

_VCACHE: Dict[Tuple[int, int, int], np.ndarray] = {}
_ICACHE: Dict[Tuple[int, int, Tuple[int, ...]], Optional[np.ndarray]] = {}
_CACHE_CAP = 512


def vandermonde(field: GF, k: int, n: int) -> np.ndarray:
    """n x k evaluation matrix: row i = [x^0 .. x^{k-1}] at x = i+1."""
    key = (field.bits, k, n)
    v = _VCACHE.get(key)
    if v is None:
        if len(_VCACHE) >= _CACHE_CAP:
            _VCACHE.clear()
        v = np.zeros((n, k), dtype=field.dtype)
        for r in range(n):
            acc = 1
            for c in range(k):
                v[r, c] = acc
                acc = field.mul(acc, r + 1)
        _VCACHE[key] = v
    return v


def _inverse_for(
    field: GF, k: int, xs: Tuple[int, ...]
) -> Optional[np.ndarray]:
    key = (field.bits, k, xs)
    if key in _ICACHE:
        return _ICACHE[key]
    if len(_ICACHE) >= _CACHE_CAP:
        _ICACHE.clear()
    mat = np.zeros((k, k), dtype=field.dtype)
    for r, x in enumerate(xs):
        acc = 1
        for c in range(k):
            mat[r, c] = acc
            acc = field.mul(acc, x)
    inv = field.mat_inv(mat)
    _ICACHE[key] = inv
    return inv


# -- device dispatch ---------------------------------------------------------

_JIT_CACHE: Dict[int, object] = {}
_EXP_DEV: Dict[int, object] = {}


def device_enabled() -> bool:
    """True when RS matmuls should dispatch to a jax device. Env knob
    LACHAIN_RS_DEVICE: "1" forces on, "0" forces off; unset asks the one
    place that decides whether this process owns a device
    (crypto/provider.device_platform) — a host-backend process answers no
    without importing jax."""
    env = os.environ.get("LACHAIN_RS_DEVICE")
    if env in ("0", "1"):
        return env == "1"
    from ..crypto.provider import device_platform

    return device_platform() not in (None, "cpu")


def _device_jit(bits: int):
    fn = _JIT_CACHE.get(bits)
    if fn is None:
        import jax

        def _mm(exp, log_a, mask_a, log_b, mask_b):
            import jax.numpy as jnp

            def body(j, acc):
                la = jax.lax.dynamic_slice_in_dim(log_a, j, 1, 1)  # (r,1)
                ma = jax.lax.dynamic_slice_in_dim(mask_a, j, 1, 1)
                lb = jax.lax.dynamic_slice_in_dim(log_b, j, 1, 0)  # (1,c)
                mb = jax.lax.dynamic_slice_in_dim(mask_b, j, 1, 0)
                prod = jnp.where(ma & mb, exp[la + lb], 0).astype(exp.dtype)
                return acc ^ prod

            import jax.numpy as jnp

            acc0 = jnp.zeros(
                (log_a.shape[0], log_b.shape[1]), dtype=exp.dtype
            )
            return jax.lax.fori_loop(0, log_a.shape[1], body, acc0)

        fn = _JIT_CACHE[bits] = jax.jit(_mm)
    return fn


def _matmul_device(field: GF, a: np.ndarray, b: np.ndarray, era=None):
    """One jitted gather+XOR matmul on the device, columns padded to a
    power of two and (when the mesh has >1 device) sharded along the
    column axis — each device owns a contiguous run of slot payloads."""
    import jax

    a = np.ascontiguousarray(a, dtype=field.dtype)
    b = np.ascontiguousarray(b, dtype=field.dtype)
    c = b.shape[1]
    ndev = jax.device_count()
    c_pad = max(ndev, 1)
    while c_pad < c:
        c_pad *= 2
    b_pad = np.zeros((b.shape[0], c_pad), dtype=field.dtype)
    b_pad[:, :c] = b
    log_a = field.log[a]
    log_b = field.log[b_pad]
    mask_a = a != 0
    mask_b = b_pad != 0
    with tracing.span(
        "rs.device",
        era=era,
        bits=field.bits,
        rows=int(a.shape[0]),
        cols=int(c),
        cols_padded=int(c_pad),
        devices=int(ndev),
    ):
        exp_dev = _EXP_DEV.get(field.bits)
        if exp_dev is None:
            exp_dev = _EXP_DEV[field.bits] = jax.device_put(field.exp)
        args = (log_b, mask_b)
        if ndev > 1 and c_pad % ndev == 0:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from ..parallel.mesh import make_mesh

            sharding = NamedSharding(make_mesh(), P(None, "shares"))
            args = tuple(jax.device_put(x, sharding) for x in args)
        out = _device_jit(field.bits)(exp_dev, log_a, mask_a, *args)
        out = np.asarray(jax.device_get(out))
    return out[:, :c]


def _matmul(field: GF, a: np.ndarray, b: np.ndarray, era=None) -> np.ndarray:
    # a device failure propagates: the numpy path is for small products
    # and host-backend processes, not a landing for exceptions
    if b.shape[1] >= _DEVICE_MIN_COLS and device_enabled():
        return _matmul_device(field, a, b, era=era)
    return field.matmul(a, b)


# -- batched codec -----------------------------------------------------------


def _coeff_matrix(field: GF, data: bytes, k: int) -> np.ndarray:
    """Length-prefix + zero-pad `data` into the k x L coefficient matrix
    (L in field symbols), mirroring ops/rs.py::encode's layout."""
    prefixed = len(data).to_bytes(4, "big") + data
    unit = k * field.sym_size
    shard_syms = (len(prefixed) + unit - 1) // unit
    shard_syms = max(shard_syms, 1)
    padded = prefixed + b"\x00" * (unit * shard_syms - len(prefixed))
    return (
        np.frombuffer(padded, dtype=field.be_dtype)
        .reshape(k, shard_syms)
        .astype(field.dtype)
    )


def encode_batch(
    items: Sequence[Tuple[bytes, int, int]], era: Optional[int] = None
) -> List[List[bytes]]:
    """Encode many (data, k, n) payloads; one matrix product per (field,
    k, n) group. Returns per-item n-shard lists, ops/rs.py-bit-identical
    for n <= 255 and GF(2^16)-coded past that."""
    results: List[Optional[List[bytes]]] = [None] * len(items)
    groups: Dict[Tuple[int, int, int], List[int]] = {}
    for idx, (data, k, n) in enumerate(items):
        assert 0 < k <= n
        field = field_for(n)
        groups.setdefault((field.bits, k, n), []).append(idx)
    for (bits, k, n), members in groups.items():
        field = GF8 if bits == 8 else gf16()
        v = vandermonde(field, k, n)
        coeffs = [_coeff_matrix(field, items[i][0], k) for i in members]
        widths = [c.shape[1] for c in coeffs]
        out = _matmul(field, v, np.concatenate(coeffs, axis=1), era=era)
        off = 0
        for i, w in zip(members, widths):
            block = out[:, off : off + w]
            off += w
            results[i] = [
                block[r].astype(field.be_dtype).tobytes() for r in range(n)
            ]
    return results  # type: ignore[return-value]


def decode_batch(
    items: Sequence[Tuple[Sequence[Optional[bytes]], int]],
    era: Optional[int] = None,
) -> List[Optional[bytes]]:
    """Decode many (shards, k) items; shards is the full n-length list with
    None for missing entries. One matrix product per (field, k, erasure
    pattern) group; per-item None on any of the scalar path's failure
    conditions (short, mixed-size, odd GF(2^16) size, bad length prefix)."""
    results: List[Optional[bytes]] = [None] * len(items)
    groups: Dict[Tuple[int, int, Tuple[int, ...]], List[int]] = {}
    sel: List[Optional[Tuple[GF, List[Tuple[int, bytes]]]]] = [None] * len(
        items
    )
    for idx, (shards, k) in enumerate(items):
        n = len(shards)
        field = field_for(n)
        have = [(i, s) for i, s in enumerate(shards) if s is not None]
        if len(have) < k:
            continue
        have = have[:k]
        size = len(have[0][1])
        if any(len(s) != size for _, s in have):
            continue  # adversarial mixed-size commitment: clean failure
        if size % field.sym_size:
            continue  # GF(2^16): odd byte length cannot be symbols
        xs = tuple(i + 1 for i, _ in have)
        sel[idx] = (field, have)
        groups.setdefault((field.bits, k, xs), []).append(idx)
    for (bits, k, xs), members in groups.items():
        field = GF8 if bits == 8 else gf16()
        inv = _inverse_for(field, k, xs)
        if inv is None:
            continue  # singular selection: every member fails cleanly
        received = []
        widths = []
        for i in members:
            _field, have = sel[i]
            mat = np.stack(
                [
                    np.frombuffer(s, dtype=field.be_dtype).astype(field.dtype)
                    for _idx, s in have
                ]
            )
            received.append(mat)
            widths.append(mat.shape[1])
        out = _matmul(field, inv, np.concatenate(received, axis=1), era=era)
        off = 0
        for i, w in zip(members, widths):
            coeffs = out[:, off : off + w]
            off += w
            flat = coeffs.astype(field.be_dtype).tobytes()
            if len(flat) < 4:
                continue
            length = int.from_bytes(flat[:4], "big")
            if length > len(flat) - 4:
                continue
            results[i] = flat[4 : 4 + length]
    return results


def encode(data: bytes, k: int, n: int) -> List[bytes]:
    """Single-item convenience (ops/rs.py delegates its n > 255 branch
    here; the differential tests drive it across both fields)."""
    return encode_batch([(data, k, n)])[0]


def decode(shards: Sequence[Optional[bytes]], k: int) -> Optional[bytes]:
    return decode_batch([(shards, k)])[0]
