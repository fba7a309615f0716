"""The per-layer metrics that read the native engine's spans and its
dispatch counter (`engine.pump`, `cross.<op>`, `era.advance`,
consensus_engine_dispatch_seconds_total): each entry of BENCHMARK.json has
its file, names a source its reader accepts, and gives a value on a traced
N=4 rehearsal of an hb64 cell on the CPU. Counts and shares only: a CPU run
says nothing about time."""
import json
import time

import pytest

from perfbench import layers, reductions, spec
from perfbench.harness import Rehearsal, run_cell
from perfbench.tests.test_rehearsal import TINY

BENCH = spec.load_benchmark()
CELLS = ["hb64.full", "hb64.quiet"]
OPS = (
    "coin_sign", "coin_combine", "coin_result", "hb_acs", "hb_queue", "hb_done",
    "root_input", "root_sign", "root_verify", "root_produce", "evidence",
    "rbc_encode", "rbc_need", "acs_result", "coin_request", "opaque_message",
)
FAMILIES = ("rbc", "ba", "coin", "tpke", "commit")


def _is_engine_metric(name: str) -> bool:
    return name.startswith(("engine_pump", "engine_native", "engine_dispatch_", "cross_")) or name in (
        "crossings_per_era", "era_unnamed_share",
    )


NEW = [m for m in BENCH["per_layer"] if _is_engine_metric(m["name"])]


def test_the_entries_are_the_ones_the_issue_lists():
    names = [m["name"] for m in NEW]
    fixed = [
        "engine_pump_s_per_era", "engine_native_s_per_era", "engine_pumps_per_era",
        "crossings_per_era", "era_unnamed_share",
    ] + [f"engine_dispatch_{fam}_s_per_era" for fam in FAMILIES]
    assert set(fixed) <= set(names)
    per_op = sorted(set(names) - set(fixed))
    # one file for each op a traced chip run read over 2% of `era` (PERF.md section 5)
    assert len(per_op) <= 8
    assert all(n[len("cross_"):-len("_s_per_era")] in OPS for n in per_op)
    # appended: what the benchmark had stays where it was
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == names


@pytest.mark.parametrize("entry", NEW, ids=lambda m: m["name"])
def test_entry_has_its_file_and_a_source_its_reader_accepts(entry):
    assert entry["workloads"] == CELLS, "the served node runs another engine"
    assert entry["layer"] == "consensus_engine" and entry["moves"] == "era_p50_s"
    metric = spec.load_layer_metric(entry["name"])
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert metric[key] == entry[key], key
    assert metric["reduction"] in reductions.REDUCTIONS
    kind = metric["read"]["kind"]
    assert layers.KIND_SOURCE[kind] == metric["source"]
    assert len(metric["why"]) > 40
    if kind == "counter":
        assert metric["read"]["name"] == "consensus_engine_dispatch_seconds_total"
        assert metric["read"]["labels"]["family"] in FAMILIES
        assert "1/k" in metric["why"], "a counter reading is high by 1/k: say so"
        return
    names = metric["read"]["names"] + metric.get("less", {}).get("names", [])
    crosses = [n for n in names if n.startswith("cross.")]
    # the reader matches names exactly: "all cross.*" is all sixteen, or one
    assert len(crosses) in (0, 1) or sorted(crosses) == sorted(f"cross.{op}" for op in OPS)


def test_unnamed_share_subtracts_what_engine_self_time_subtracts():
    own = spec.load_layer_metric("era_unnamed_share")
    older = spec.load_layer_metric("engine_self_s_per_era")
    assert own["read"] == older["read"]
    assert set(older["less"]["names"]) < set(own["less"]["names"])
    assert {"engine.pump", "era.advance"} < set(own["less"]["names"])


@pytest.fixture(scope="module")
def traced_line():
    line = run_cell(
        "hb64.quiet", 11, 3.0, True, time.monotonic(), rehearsal=Rehearsal(config=TINY)
    )
    return json.loads(json.dumps(line))


@pytest.mark.parametrize("entry", NEW, ids=lambda m: m["name"])
def test_entry_gives_a_value_on_a_traced_rehearsal(traced_line, entry):
    assert traced_line["correct"] is True
    got = traced_line["metrics"][entry["name"]]
    assert got["unit"] == entry["unit"]
    if entry["name"] == "era_unnamed_share":
        assert 0.0 <= got["value"] < 100.0
    else:
        assert got["value"] > 0.0


def test_the_readings_agree_with_each_other(traced_line):
    value = {k: v["value"] for k, v in traced_line["metrics"].items()}
    assert 0 < value["engine_native_s_per_era"] < value["engine_pump_s_per_era"]
    assert value["engine_pumps_per_era"] >= 1
    # N=4, every protocol native-owned: per validator ten callbacks an era
    # (more where an agreement takes another coin) and N rbc_need
    assert value["crossings_per_era"] >= 4 * (10 + 4)
    per_op = sum(v for k, v in value.items() if k.startswith("cross_"))
    assert per_op + value["engine_native_s_per_era"] > 0


def test_served_node_cells_carry_none_of_them():
    for workload in ("hb7.full", "hb7.quiet"):
        cell = spec.load_cell(workload)
        assert not [m["name"] for m in cell.per_layer if _is_engine_metric(m["name"])]
