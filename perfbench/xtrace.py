"""Reader for the jax profiler's trace: from an .xplane.pb (or its plain-JSON
dump, which is what the tests keep) to device operations on the host's
monotonic clock, device busy seconds, idle gaps named by what the host was
doing, and the operations that took most time.

The trace's own clock starts at the profile's start. The harness writes one
`perfbench.sync` TraceAnnotation carrying time.monotonic_ns(); its position
in the trace gives the offset, so device events, the program's tracer spans
and the benchmark's own spans share one axis.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import reductions as R

SYNC_NAME = "perfbench.sync"


@dataclass(frozen=True)
class Op:
    """One event of a device line, seconds on the monotonic clock."""

    name: str
    start: float
    end: float
    device: str
    line: str


def dump_xplane(path: str) -> dict:
    """The trace as plain data: {"planes": [{"name", "lines": [{"name",
    "events": [[name, start_ns, duration_ns, {stat: value}], ...]}]}]}."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                stats = {}
                if ev.name == SYNC_NAME:
                    stats = {k: v for k, v in ev.stats}
                events.append(
                    [ev.name, float(ev.start_ns), float(ev.duration_ns), stats]
                )
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def sync_offset_ns(trace: dict) -> float:
    """monotonic_ns minus trace_ns, from the harness's sync annotation."""
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for name, start_ns, _dur, stats in line["events"]:
                if name == SYNC_NAME and "mono_ns" in stats:
                    return float(stats["mono_ns"]) - start_ns
    raise ValueError(f"the trace holds no {SYNC_NAME} annotation")


def device_ops(
    trace: dict, plane_prefix: str, lines: Sequence[str]
) -> List[Op]:
    """Events of the named lines of every device plane, on the monotonic
    clock, sorted by start."""
    off = sync_offset_ns(trace)
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(plane_prefix):
            continue
        for line in plane["lines"]:
            if line["name"] not in lines:
                continue
            for name, start_ns, dur_ns, _stats in line["events"]:
                start = (start_ns + off) / 1e9
                out.append(
                    Op(name, start, start + dur_ns / 1e9, plane["name"], line["name"])
                )
    out.sort(key=lambda op: op.start)
    return out


def busy_by_device(ops: Iterable[Op]) -> Dict[str, List[R.Interval]]:
    per: Dict[str, list] = {}
    for op in ops:
        per.setdefault(op.device, []).append((op.start, op.end))
    return {dev: R.union(ivs) for dev, ivs in per.items()}


def busy_seconds(ops: Iterable[Op], lo: float, hi: float) -> float:
    """Seconds in [lo, hi] during which an operation ran on the device,
    averaged over the devices that ran any."""
    per = busy_by_device(ops)
    if not per:
        return 0.0
    return sum(R.total(R.clip(b, lo, hi)) for b in per.values()) / len(per)


_HLO = re.compile(r"^(%[^\s=]+) = .*?\s([a-z][\w\-]*)\(")


def op_label(name: str) -> str:
    """A device op's short name. The trace names an op by its whole HLO line
    (`%branch_0_fun.38 = (s32[132,128]{...}) custom-call(...)`): keep the
    opcode and the result's name, less the digits that only number an
    instance, so that a kernel's calls add up."""
    m = _HLO.match(name)
    head = f"{m.group(2)} {m.group(1)}" if m else name
    return re.sub(r"[.\d]+$", "", head) or head


# ops that only hold other ops of the same line: their time is their body's
_CONTAINERS = ("while ", "conditional ", "call ")


def top_ops(ops: Iterable[Op], limit: int = 10) -> List[list]:
    """[[name, seconds], ...] by total device time, containers left out so
    that no second is counted twice."""
    totals: Dict[str, float] = {}
    for op in ops:
        key = op_label(op.name)
        if key.startswith(_CONTAINERS):
            continue
        totals[key] = totals.get(key, 0.0) + (op.end - op.start)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [[name, secs] for name, secs in ranked[:limit]]


def label_timeline(
    spans: Sequence[Tuple[str, float, float]], lo: float, hi: float
) -> List[Tuple[float, float, str]]:
    """Paint [lo, hi] with span names, the shortest covering span winning
    (the innermost thing the host was doing). Unpainted stretches carry the
    label "(no span)"."""
    edges = {lo, hi}
    for _name, a, b in spans:
        if b > lo and a < hi:
            edges.add(max(a, lo))
            edges.add(min(b, hi))
    cuts = sorted(edges)
    order = sorted((a, b, name) for name, a, b in spans if b > lo and a < hi)
    active: list = []  # (duration, end, name) of the spans covering `left`
    out: List[Tuple[float, float, str]] = []
    nxt = 0
    for left, right in zip(cuts, cuts[1:]):
        while nxt < len(order) and order[nxt][0] <= left:
            a, b, name = order[nxt]
            active.append((b - a, b, name))
            nxt += 1
        active = [e for e in active if e[1] > left]
        label = min(active)[2] if active else "(no span)"
        if out and out[-1][2] == label and out[-1][1] == left:
            out[-1] = (out[-1][0], right, label)
        else:
            out.append((left, right, label))
    return out


def idle_by_host_span(
    ops: Sequence[Op],
    spans: Sequence[Tuple[str, float, float]],
    lo: float,
    hi: float,
) -> Dict[str, float]:
    """{name: seconds}: the device's idle time in [lo, hi], split by what
    the host was doing meanwhile. With several devices a stretch counts as
    idle only while all of them are."""
    busy = R.union(iv for b in busy_by_device(ops).values() for iv in b)
    idle = R.gaps(busy, lo, hi)
    totals: Dict[str, float] = {}
    for a, b, label in label_timeline(spans, lo, hi):
        secs = R.total(R.clip(idle, a, b))
        if secs > 0:
            totals[label] = totals.get(label, 0.0) + secs
    return totals


def matching(ops: Iterable[Op], line: Optional[str], pattern: str) -> List[Op]:
    rx = re.compile(pattern)
    return [
        op
        for op in ops
        if (line is None or op.line == line) and rx.search(op.name)
    ]
