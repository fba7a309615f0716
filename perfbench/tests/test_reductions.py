"""The benchmark's arithmetic, on small inputs whose answers are known, and
the trace reduction on a small recorded trace kept in perfbench/testdata."""
import json
import os

import pytest

from perfbench import layers, xtrace
from perfbench import reductions as R

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata")


def test_percentile_interpolates_between_ranks():
    assert R.percentile([4.0], 95) == 4.0
    assert R.percentile([1, 2, 3, 4, 5], 50) == 3
    assert R.percentile([1, 2, 3, 4], 50) == 2.5
    assert R.percentile(list(range(101)), 95) == 95
    with pytest.raises(ValueError):
        R.percentile([], 50)


def test_union_clip_gaps():
    busy = R.union([(3, 4), (0, 1), (0.5, 2), (2, 2)])
    assert busy == [(0, 2), (3, 4)]
    assert R.total(busy) == 3
    assert R.clip(busy, 1, 3.5) == [(1, 2), (3, 3.5)]
    assert R.gaps(busy, -1, 5) == [(-1, 0), (2, 3), (4, 5)]
    assert R.gaps([], 0, 2) == [(0, 2)]


def test_self_time_is_parent_less_what_children_cover():
    # children overlap each other and stick out of the parent
    assert R.self_times([(0, 10)], [(1, 3), (2, 4), (9, 12)]) == [10 - 3 - 1]
    assert R.self_times([(0, 1), (5, 6)], []) == [1, 1]


@pytest.mark.parametrize(
    "name,kw,want",
    [
        ("count", {}, 3.0),
        ("sum", {}, 6.0),
        ("p50", {}, 2.0),
        ("last", {}, 3.0),
        ("per_era", {"eras": 2}, 3.0),
        ("count_per_era", {"eras": 2}, 1.5),
        ("share_of_window", {"window_s": 12.0}, 0.5),
        ("share_of_count", {"population": 6}, 0.5),
        ("share_of_source", {"source_total": 24.0}, 0.25),
    ],
)
def test_reductions(name, kw, want):
    assert R.reduce(name, [1.0, 2.0, 3.0], **kw) == want


def test_reduction_of_nothing_is_nothing():
    assert R.reduce("p50", []) is None
    assert R.reduce("per_era", [1.0], eras=0) is None
    assert R.reduce("count", []) == 0.0
    with pytest.raises(ValueError):
        R.reduce("mean", [1.0])


def _trace():
    with open(os.path.join(TESTDATA, "small_trace.json"), encoding="utf-8") as fh:
        return json.load(fh)


def test_recorded_trace_reduces_to_known_busy_and_idle():
    """The recorded trace is a v5e run of this benchmark cut down by hand to a
    few events; its expected numbers are kept beside it."""
    trace = _trace()
    want = trace["expected"]
    ops = xtrace.device_ops(trace, "/device:TPU:", ["XLA Ops", "XLA Modules"])
    busy_ops = [op for op in ops if op.line == "XLA Ops"]
    lo, hi = want["slice"]
    busy = xtrace.busy_seconds(busy_ops, lo, hi)
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert 1 - busy / (hi - lo) == pytest.approx(want["idle_share"], rel=1e-9)
    kernels = xtrace.matching(ops, "XLA Modules", want["kernel_pattern"])
    assert sum(op.end - op.start for op in kernels) == pytest.approx(
        want["kernel_s"], rel=1e-9
    )
    assert [n for n, _s in xtrace.top_ops(busy_ops)][:2] == want["top_ops"]
    idle = xtrace.idle_by_host_span(
        busy_ops, [tuple(s) for s in want["host_spans"]], lo, hi
    )
    assert idle == pytest.approx(want["idle_by_span"], rel=1e-9)
    assert sum(idle.values()) == pytest.approx((hi - lo) - busy, rel=1e-9)


def test_trace_without_sync_annotation_is_refused():
    trace = _trace()
    for plane in trace["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"] if e[0] != xtrace.SYNC_NAME]
    with pytest.raises(ValueError):
        xtrace.device_ops(trace, "/device:TPU:", ["XLA Ops"])


def test_innermost_host_span_names_a_stretch():
    spans = [("era", 0.0, 10.0), ("flush", 2.0, 5.0), ("call", 3.0, 4.0)]
    assert xtrace.label_timeline(spans, 1.0, 11.0) == [
        (1.0, 2.0, "era"),
        (2.0, 3.0, "flush"),
        (3.0, 4.0, "call"),
        (4.0, 5.0, "flush"),
        (5.0, 10.0, "era"),
        (10.0, 11.0, "(no span)"),
    ]


def _span(name, start, end, **args):
    return {"name": name, "cat": "era", "start": start, "end": end, "open": False, "args": args}


def test_layer_metric_readers():
    obs = layers.Observations(
        window=(0.0, 20.0),
        era_ends=[10.0, 20.0],
        spans=[
            _span("era", 0.0, 10.0, outcome="consensus"),
            _span("era", 10.0, 20.0, outcome="synced"),
            _span("tpke.flush", 1.0, 3.0),
            _span("exec.block", 12.0, 13.0),
            _span("era", 20.5, 30.0, outcome="consensus"),  # after the window
        ],
        counter_delta=lambda name, labels: 6.0 if labels == {"path": "device"} else 0.0,
        proxy_calls=[("tpke_era_verify_combine", 1.5, 2.5, 64)],
        ops=[xtrace.Op("jit_era_kernel_packed(1)", 1.75, 2.0, "/device:TPU:0", "XLA Modules")],
        slices={"window": [(0.0, 10.0)], "replay": []},
        bench={"late": [0.01, 0.03]},
    )
    read = {"kind": "span", "names": ["era"]}
    self_s = {
        "name": "m", "source": "program_span", "read": read,
        "less": {"kind": "span", "names": ["tpke.flush", "exec.block"]},
        "reduction": "per_era",
    }
    assert layers.evaluate(self_s, obs) == (20 - 2 - 1) / 2
    share = {
        "name": "m", "source": "program_span", "reduction": "share_of_count", "scale": 100,
        "read": {**read, "where": {"outcome": "consensus"}},
    }
    assert layers.evaluate(share, obs) == 50.0
    counter = {
        "name": "m", "source": "program_counter", "reduction": "per_era",
        "read": {"kind": "counter", "name": "c", "labels": {"path": "device"}},
    }
    assert layers.evaluate(counter, obs) == 3.0
    marshal = {
        "name": "m", "source": "host_clock", "reduction": "share_of_source", "scale": 100,
        "read": {"kind": "proxy", "method": "tpke_era_verify_combine", "slice": "window"},
        "less": {"kind": "trace", "line": "XLA Modules", "pattern": "^jit_era_kernel"},
    }
    assert layers.evaluate(marshal, obs) == 75.0
    kernel = {
        "name": "m", "source": "device_trace", "reduction": "per_era", "scale": 1000,
        "read": {"kind": "trace", "line": "XLA Modules", "pattern": "^jit_era_kernel"},
    }
    assert layers.evaluate(kernel, obs) == 250.0  # one era ends inside the slice
    nothing = {
        "name": "m", "source": "program_span", "reduction": "p50",
        "read": {"kind": "span", "names": ["rbc.flush"]},
    }
    assert layers.evaluate(nothing, obs) is None
    late = {
        "name": "m", "source": "host_clock", "reduction": "p95", "scale": 1000,
        "read": {"kind": "bench", "name": "late"},
    }
    assert layers.evaluate(late, obs) == pytest.approx(29.0)
    with pytest.raises(ValueError):
        layers.evaluate({**kernel, "source": "host_clock"}, obs)
