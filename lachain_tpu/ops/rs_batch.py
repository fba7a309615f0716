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
wants. On the host (numpy) a product is a log/exp table gather plus an XOR
reduction over the contraction axis. When this process owns an accelerator
(crypto/provider.py decides; or LACHAIN_RS_DEVICE=1 forces it) products of
at least _DEVICE_MIN_COLS columns run on the device as ONE 0/1 matrix
product on the matrix unit instead: multiplying by a constant `a` is linear
over GF(2), an (bits x bits) bit matrix, so `A (r,k) x B (k,c)` is
`A_bin (bits*r, bits*k) @ B's bit planes (bits*k, c)`, reduced mod 2 and
packed back into symbols (bit_matrix, _mm). Its columns are sharded across
the device mesh (parallel/mesh.py) along the column (slot-payload) axis.
Both paths compute the same field arithmetic exactly, so results are
bit-identical to ops/rs.py (tests/test_rs_batch.py pins a 200-seed
differential, and the device product against GF.matmul).

GF(2^16) (poly x^16+x^12+x^3+x+1 = 0x1100B, generator 2) backs shard counts
past GF(2^8)'s 255 evaluation points: symbols are big-endian uint16 pairs,
shard byte sizes are even, and an odd-sized shard is a clean decode failure.
This removes the n > 255 whole-payload replication fallback that capped
honest coding at N=255 (consensus_rt.cpp keeps replication as its
engine-internal fallback when no host shim is attached).
"""
from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import metrics, tracing

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

    def bit_matrix(self, a: np.ndarray) -> np.ndarray:
        """Multiplication by `a` (r,k) as a 0/1 matrix over GF(2), shape
        (bits*r, bits*k): row block o, column block i holds bit o of
        a * 2^i, so that bit_matrix(a) @ planes(b) mod 2 = planes(a x b),
        where planes(b) stacks bit i of every symbol of b as row block i."""
        a = np.ascontiguousarray(a, dtype=self.dtype)
        r, k = a.shape
        bits = self.bits
        # a * 2^i = exp[log a + i] (2 generates the field); 0 stays 0
        powers = np.where(
            a != 0, self.exp[self.log[a] + np.arange(bits)[:, None, None]], 0
        ).transpose(1, 0, 2)  # (r, i, k)
        out = np.empty((bits, r, bits, k), dtype=np.uint8)
        for o in range(bits):
            out[o] = (powers >> o) & 1
        return out.reshape(bits * r, bits * k)

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
# the device product's bit matrices, under the key of the matrix they
# expand (a _VCACHE or an _ICACHE key)
_BCACHE: Dict[tuple, np.ndarray] = {}
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


def _bit_matrix_for(field: GF, a: np.ndarray, key: tuple) -> np.ndarray:
    """field.bit_matrix(a), cached under `key`, the key of `a` itself."""
    a_bin = _BCACHE.get(key)
    if a_bin is None:
        if len(_BCACHE) >= _CACHE_CAP:
            _BCACHE.clear()
        a_bin = _BCACHE[key] = field.bit_matrix(a)
    return a_bin


# -- device dispatch ---------------------------------------------------------


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


def _mm(a_bin, b):
    """The device's GF product: `b` (k, c) of symbols (uint8 for GF(2^8),
    uint16 for GF(2^16): the field's width is read from it) into bit planes
    (bits*k, c), one exact 0/1 matmul against a_bin (bits*r, bits*k) on the
    matrix unit (bf16 in, f32 sums of at most bits*k ones), mod 2, packed
    back into (r, c) symbols."""
    import jax.numpy as jnp

    bits = b.dtype.itemsize * 8
    k, c = b.shape
    shifts = jnp.arange(bits, dtype=b.dtype)
    planes = (b[None] >> shifts[:, None, None]) & 1  # (i, k, c)
    sums = jnp.dot(
        a_bin.astype(jnp.bfloat16),
        planes.reshape(bits * k, c).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    out_bits = (sums.astype(jnp.int32) & 1).reshape(bits, -1, c)  # (o, r, c)
    weights = shifts.astype(jnp.int32)[:, None, None]
    return jnp.sum(out_bits << weights, axis=0).astype(b.dtype)


@functools.cache
def _device_jit():
    import jax

    return jax.jit(_mm)


def _matmul_device(field: GF, a: np.ndarray, b: np.ndarray, key: tuple, era=None):
    """One jitted bit-plane matmul on the device (_mm), columns padded to a
    power of two and (when the mesh has >1 device) sharded along the
    column axis — each device owns a contiguous run of slot payloads, and
    the bit matrix of `a` (cached under `key`) is replicated."""
    import jax

    a_bin = _bit_matrix_for(field, a, key)
    b = np.ascontiguousarray(b, dtype=field.dtype)
    c = b.shape[1]
    ndev = jax.device_count()
    c_pad = max(ndev, 1)
    while c_pad < c:
        c_pad *= 2
    b_pad = np.zeros((b.shape[0], c_pad), dtype=field.dtype)
    b_pad[:, :c] = b
    with tracing.span(
        "rs.device",
        era=era,
        bits=field.bits,
        rows=int(a.shape[0]),
        cols=int(c),
        cols_padded=int(c_pad),
        devices=int(ndev),
    ):
        args = (a_bin, b_pad)
        if ndev > 1 and c_pad % ndev == 0:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from ..parallel.mesh import make_mesh

            mesh = make_mesh()
            args = (
                jax.device_put(a_bin, NamedSharding(mesh, P())),
                jax.device_put(b_pad, NamedSharding(mesh, P(None, "shares"))),
            )
        out = np.asarray(jax.device_get(_device_jit()(*args)))
    return out[:, :c]


def _matmul(
    field: GF, a: np.ndarray, b: np.ndarray, key: tuple, era=None
) -> np.ndarray:
    # a device failure propagates: the numpy path is for small products
    # and host-backend processes, not a landing for exceptions
    if b.shape[1] >= _DEVICE_MIN_COLS and device_enabled():
        metrics.inc("rs_matmul_total", labels={"path": "device"})
        return _matmul_device(field, a, b, key, era=era)
    metrics.inc("rs_matmul_total", labels={"path": "host"})
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
        out = _matmul(
            field, v, np.concatenate(coeffs, axis=1), (bits, k, n), era=era
        )
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
        out = _matmul(
            field, inv, np.concatenate(received, axis=1), (bits, k, xs), era=era
        )
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
