"""The one reader of per-layer metrics. A metric is a data file
(perfbench/layer_metrics/<name>.json) that names where its readings come
from (`read`, and optionally `less`, which is subtracted as covered time)
and one reduction of reductions.REDUCTIONS. Kinds of `read`:

  span     the program's tracer spans by name (utils/tracing.py), optionally
           only those whose args match `where`
  counter  the change of a program counter over the window
  proxy    the benchmark's timings at the backend seam (proxy.py)
  trace    device operations of the profiler trace whose name matches
           `pattern` on `line`
  bench    values the harness or the driver measured itself, by name

A reader that finds nothing to read returns None and the metric is left out
of the line. `"slice": "window"` narrows span and proxy readings to the
profiled slice of the window, which trace readings are by default;
`"slice": "replay"` reads the kept batch's device pass after the window.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import reductions as R
from . import xtrace

KIND_SOURCE = {
    "span": "program_span",
    "counter": "program_counter",
    "trace": "device_trace",
    "proxy": "host_clock",
}


@dataclass
class Observations:
    window: Tuple[float, float]  # window start .. end of the last complete era
    era_ends: List[float]  # commit times of the eras that count
    spans: List[dict] = field(default_factory=list)  # tracing.snapshot()
    counter_delta: Callable[[str, Optional[dict]], float] = lambda n, l: 0.0
    proxy_calls: List[Tuple[str, float, float, int]] = field(default_factory=list)
    ops: List[xtrace.Op] = field(default_factory=list)
    # profiled slices by tag: "window", "replay"
    slices: Dict[str, List[Tuple[float, float]]] = field(default_factory=dict)
    bench: Dict[str, List[float]] = field(default_factory=dict)

    def eras_in(self, spans: Sequence[Tuple[float, float]]) -> int:
        return sum(
            1 for t in self.era_ends if any(lo < t <= hi for lo, hi in spans)
        )


def _starts_inside(t: float, scopes) -> bool:
    return any(lo <= t <= hi for lo, hi in scopes)


def _inside(ivs, scopes):
    """Intervals that start inside one of the scopes."""
    return [iv for iv in ivs if _starts_inside(iv[0], scopes)]


def _intervals(read: dict, obs: Observations):
    """(intervals, population, scopes) for an interval-valued source."""
    kind = read["kind"]
    tag = read.get("slice", "window" if kind == "trace" else None)
    scopes = obs.slices.get(tag, []) if tag else [obs.window]
    if kind == "span":
        named = [
            s
            for s in obs.spans
            if s["name"] in read["names"]
            and not s["open"]
            and s["end"] > s["start"]
            and _starts_inside(s["start"], scopes)
        ]
        population = len(named)
        where = read.get("where")
        if where:
            named = [
                s
                for s in named
                if all(s["args"].get(k) == v for k, v in where.items())
            ]
        return [(s["start"], s["end"]) for s in named], population, scopes
    if kind == "proxy":
        ivs = [(a, b) for m, a, b, _n in obs.proxy_calls if m == read["method"]]
        ivs = _inside(ivs, scopes)
        return ivs, len(ivs), scopes
    if kind == "trace":
        ops = xtrace.matching(obs.ops, read.get("line"), read["pattern"])
        ivs = _inside([(op.start, op.end) for op in ops], scopes)
        return ivs, len(ivs), scopes
    raise ValueError(f"layer metric: unknown read kind {kind!r}")


def evaluate(metric: dict, obs: Observations) -> Optional[float]:
    read = metric["read"]
    kind = read["kind"]
    # a `bench` reading is whatever the harness took it from; its file says
    if kind != "bench" and KIND_SOURCE.get(kind) != metric["source"]:
        raise ValueError(
            f"{metric['name']}: reads a {kind} but says source {metric['source']}"
        )
    scale = float(metric.get("scale", 1))
    if kind == "counter":
        value = obs.counter_delta(read["name"], read.get("labels"))
        durations, population, scopes = [value], 1, [obs.window]
        source_total = value
    elif kind == "bench":
        durations = list(obs.bench.get(read["name"], []))
        population, scopes, source_total = len(durations), [obs.window], sum(durations)
    else:
        ivs, population, scopes = _intervals(read, obs)
        source_total = R.total(ivs)
        if "less" in metric:
            less, _pop, _sc = _intervals(metric["less"], obs)
            durations = R.self_times(ivs, less)
        else:
            durations = [b - a for a, b in ivs]
    value = R.reduce(
        metric["reduction"],
        durations,
        eras=obs.eras_in(scopes),
        window_s=sum(hi - lo for lo, hi in scopes),
        population=population,
        source_total=source_total,
    )
    return None if value is None else value * scale
