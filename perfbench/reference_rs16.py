"""A plain Reed-Solomon codec over GF(2^16), written from the field's
definition and the shard layout alone: the oracle the program's codec is
held to where RBC codes past 255 shards (tests/test_reference_rs16.py,
tests/test_committee_script.py, chip_smoke.py). It shares no code with the
program's codec (lachain_tpu/ops/): a product here is a table lookup over
tables built by repeated doubling, encoding is Horner's rule at each
evaluation point, and decoding is Gauss-Jordan elimination over the first k
shards present.

The field: GF(2)[x] / (x^16 + x^12 + x^3 + x + 1), generator 2. The code:
a payload is prefixed with its length (4 bytes, big-endian), zero-padded to
k * 2 * L bytes and read as k rows of L big-endian 16-bit symbols, the
coefficients c_0 .. c_{k-1} of a polynomial in each of the L columns; shard
i (0-based) holds that polynomial evaluated at x = i + 1 in every column,
as L big-endian symbols. Any k shards give the payload back.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

POLY = 0x1100B  # x^16 + x^12 + x^3 + x + 1
ORDER = (1 << 16) - 1  # the multiplicative group's order


def _tables():
    """exp[i] = 2^i for i in [0, 2 * ORDER); log[2^i] = i."""
    exp = np.zeros(2 * ORDER, dtype=np.int64)
    log = np.zeros(1 << 16, dtype=np.int64)
    x = 1
    for i in range(ORDER):
        exp[i] = x
        log[x] = i
        x <<= 1  # times the generator 2
        if x & 0x10000:
            x ^= POLY  # reduce by the field's polynomial
    if x != 1:
        raise AssertionError("2 does not generate GF(2^16) under this polynomial")
    exp[ORDER:] = exp[:ORDER]
    return exp, log


EXP, LOG = _tables()


def mul(a: np.ndarray, b) -> np.ndarray:
    """Elementwise product of symbol arrays (b may be a scalar or broadcast)."""
    a = np.asarray(a, dtype=np.int64)
    b = np.asarray(b, dtype=np.int64)
    out = EXP[LOG[a] + LOG[b]]
    return np.where((a == 0) | (b == 0), 0, out)


def inverse(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse")
    return int(EXP[ORDER - LOG[a]])


def _coefficients(data: bytes, k: int) -> np.ndarray:
    """The payload as the k x L coefficient matrix."""
    prefixed = len(data).to_bytes(4, "big") + data
    unit = 2 * k
    width = max(-(-len(prefixed) // unit), 1)
    padded = prefixed + bytes(unit * width - len(prefixed))
    return np.frombuffer(padded, dtype=">u2").astype(np.int64).reshape(k, width)


def _evaluate(coeffs: np.ndarray, n: int) -> np.ndarray:
    """Horner's rule at x = 1 .. n in every column: (n, L)."""
    xs = np.arange(1, n + 1, dtype=np.int64)[:, None]
    acc = np.broadcast_to(coeffs[-1], (n, coeffs.shape[1])).copy()
    for row in coeffs[-2::-1]:
        acc = mul(acc, xs) ^ row
    return acc


def encode(data: bytes, k: int, n: int) -> List[bytes]:
    """The n shards of `data`, any k of which rebuild it."""
    return encode_many([data], k, n)[0]


def encode_many(payloads: Sequence[bytes], k: int, n: int) -> List[List[bytes]]:
    """encode() of each payload; the columns of all are evaluated at once."""
    if not 0 < k <= n <= ORDER:
        raise ValueError(f"no code with k={k}, n={n} over GF(2^16)")
    blocks = [_coefficients(p, k) for p in payloads]
    values = _evaluate(np.concatenate(blocks, axis=1), n).astype(">u2")
    out, at = [], 0
    for block in blocks:
        width = block.shape[1]
        part = values[:, at : at + width]
        at += width
        out.append([part[i].tobytes() for i in range(n)])
    return out


def decode(shards: Sequence[Optional[bytes]], k: int) -> Optional[bytes]:
    """The payload from the first k shards present (None marks a missing
    one); None where fewer than k are present, their sizes differ or are
    odd, the k points give no solution, or the length prefix overruns."""
    have = [(i, s) for i, s in enumerate(shards) if s is not None][:k]
    if len(have) < k:
        return None
    size = len(have[0][1])
    if size % 2 or any(len(s) != size for _i, s in have):
        return None
    # [V | R]: row r is x_r^0 .. x_r^{k-1} beside shard r's symbols
    rows = []
    for i, s in have:
        x, power, row = i + 1, 1, []
        for _c in range(k):
            row.append(power)
            power = int(mul(power, x))
        rows.append(row + list(np.frombuffer(s, dtype=">u2").astype(np.int64)))
    m = np.array(rows, dtype=np.int64).reshape(k, k + size // 2)
    for col in range(k):
        pivot = next((r for r in range(col, k) if m[r, col]), None)
        if pivot is None:
            return None
        m[[col, pivot]] = m[[pivot, col]]
        m[col] = mul(m[col], inverse(int(m[col, col])))
        for r in range(k):
            if r != col and m[r, col]:
                m[r] ^= mul(m[col], int(m[r, col]))
    flat = m[:, k:].astype(">u2").tobytes()
    length = int.from_bytes(flat[:4], "big")
    if length > len(flat) - 4:
        return None
    return flat[4 : 4 + length]
