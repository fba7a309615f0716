"""Roofline shares: the operations and bytes a device program needs, counted
from the shapes its span carries, against the device time the profiler
trace gives it and the chip's peaks (perfbench/peaks.json).

A product of RBC's Reed-Solomon codec (ops/rs_batch.py `_mm`, span
`rs.device`, args bits, rows, k, cols_padded) multiplies A's bit matrix,
(bits*rows) x (bits*k) of 0/1, by B's bit planes, (bits*k) x cols_padded,
on the matrix unit: 2*(bits*rows)*(bits*k)*cols_padded flops. Its bytes are
what it reads and writes in HBM: the bit matrix (a byte an entry), B and
the result (k and rows symbols of bits/8 bytes a column). The least time
of a product is the larger of flops over the bf16 peak and bytes over the
HBM bandwidth; the share is the sum of those over the summed device time of
the products' modules. It stays under 1: the count is the matmul's alone,
while the module also unpacks B's planes, reduces mod 2 and packs the
result. A reading above 1 would mean the count or the time is wrong.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

from . import reductions as R
from . import xtrace

RS_MODULE = "^jit__mm"  # the `XLA Modules` events of _mm's program


def rs_product_work(args: dict) -> Optional[Tuple[float, float]]:
    """(flops, bytes) of one RS product from its span's args; None for a
    span that lacks a dimension (a program before `k` was recorded)."""
    try:
        bits, rows, k, cols = (
            int(args[key]) for key in ("bits", "rows", "k", "cols_padded")
        )
    except (KeyError, TypeError, ValueError):
        return None
    flops = 2.0 * (bits * rows) * (bits * k) * cols
    sym = bits // 8
    nbytes = float((bits * rows) * (bits * k) + (k + rows) * cols * sym)
    return flops, nbytes


def rs_roofline_share(
    spans: Sequence[dict],
    ops: Sequence[xtrace.Op],
    scopes: Sequence[Tuple[float, float]],
    peak_flops: float,
    peak_bytes_per_s: float,
) -> Optional[float]:
    """The RS products' share of the roofline over the profiled scopes: the
    products whose `rs.device` span starts in a scope against the device
    time of the `_mm` modules that start in one. None where either side
    is empty."""

    def inside(t: float) -> bool:
        return any(lo <= t <= hi for lo, hi in scopes)

    works = []
    for s in spans:
        if s["name"] == "rs.device" and not s["open"] and inside(s["start"]):
            work = rs_product_work(s["args"])
            if work is None:
                return None
            works.append(work)
    busy = R.total(
        (op.start, op.end)
        for op in xtrace.matching(ops, "XLA Modules", RS_MODULE)
        if inside(op.start)
    )
    if not works or busy <= 0:
        return None
    least = sum(max(f / peak_flops, b / peak_bytes_per_s) for f, b in works)
    return least / busy
