"""The cell `hb7-smallbank.full` rehearsed on the CPU (N=4, 64-transaction
blocks, so that blocks go through the lane pipeline): its per-layer entries
have their files, give values on a traced run, and `correct` rests on the
VM-free dict model — a store whose word is flipped is reported incorrect.
Counts only: a CPU run says nothing about time."""
import json
import time

import pytest

from perfbench import counter_ratios, reductions, spec
from perfbench.harness import Rehearsal, run_cell
from perfbench.tests.test_rehearsal import TINY

CELL = "hb7-smallbank.full"
BENCH = spec.load_benchmark()
NEW = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
NAMES = [
    "vm_call_s_per_era", "vm_calls_per_era", "vm_interpreted_calls_per_era",
    "contract_storage_writes_per_era", "exec_largest_lane_share",
    "exec_straggler_share", "exec_merge_s_per_era",
]
# blocks of 64 reach the lanes (parallel_exec.MIN_PARALLEL_TXS = 32)
SMALL = {**TINY, "txs_per_block": 64}


def test_the_entries_are_the_ones_the_issue_lists():
    assert [m["name"] for m in NEW] == NAMES
    assert [m["name"] for m in BENCH["per_layer"]][-len(NAMES):] == NAMES
    config = next(c for c in BENCH["configs"] if c["name"] == "hb7-smallbank")
    assert config["reduced"] == ["block_interval_s"]
    cell = spec.load_cell(CELL)
    assert cell.config["reduced"] == ["block_interval_s"]
    assert {"accounts", "key skew", "operation weights", "amounts", "signing clients",
            "preload", "gas"} <= set(cell.config["assumed"])
    assert {m["name"] for m in cell.end_to_end} == {"era_p50_s", "tx_per_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    for m in BENCH["per_layer"]:
        if "hb7.full" in m.get("workloads", []):
            assert m["name"] in reported, "every list that holds hb7.full holds the cell"


@pytest.mark.parametrize("entry", NEW, ids=lambda m: m["name"])
def test_entry_has_its_file(entry):
    metric = spec.load_layer_metric(entry["name"])
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert metric[key] == entry[key], key
    assert entry["moves"] == "tx_per_s" and entry["layer"] in ("vm", "execution_storage")
    assert metric["reduction"] in reductions.REDUCTIONS and len(metric["why"]) > 40


def test_ratio_metrics_name_their_counters_and_any_driver_can_take_them():
    """The two shares are data: `read.ratio` in the metric's file, taken by
    counter_ratios.note from two readings of the registry."""

    class Bench:
        def __init__(self):
            self.noted = {}

        def note(self, name, value):
            self.noted[name] = value

    per_layer = spec.load_cell(CELL).per_layer
    assert sorted(m["name"] for m in per_layer if "ratio" in m["read"]) == [
        "exec_largest_lane_share", "exec_straggler_share",
    ]
    key = lambda name, **labels: (name, tuple(sorted(labels.items())))
    before = {key("exec_txs_validated_total"): 100.0, key("exec_txs_straggler_total"): 0.0,
              key("exec_lane_txs_largest_total"): 40.0}
    after = {key("exec_txs_validated_total"): 280.0, key("exec_txs_straggler_total"): 20.0,
             key("exec_lane_txs_largest_total"): 90.0, key("other_total", a="b"): 5.0}
    bench = Bench()
    counter_ratios.note(bench, per_layer, before, after)
    assert bench.noted == {"exec_largest_lane_share": 0.25, "exec_straggler_share": 0.1}
    # a program without the new counter: only what it has is taken; no block
    # through the lanes: nothing is
    older = {k: v for k, v in after.items() if k[0] != "exec_lane_txs_largest_total"}
    bench = Bench()
    counter_ratios.note(bench, per_layer, before, older)
    assert bench.noted == {"exec_straggler_share": 0.1}
    bench = Bench()
    counter_ratios.note(bench, per_layer, after, after)
    assert bench.noted == {}


@pytest.fixture(scope="module")
def traced_line():
    line = run_cell(CELL, 2147489011, 4.0, True, time.monotonic(), rehearsal=Rehearsal(config=SMALL))
    return json.loads(json.dumps(line))


@pytest.mark.parametrize("name", NAMES)
def test_entry_gives_a_value_on_a_traced_rehearsal(traced_line, name):
    assert traced_line["correct"] is True and traced_line["failed"] == 0
    value = traced_line["metrics"][name]["value"]
    if name in ("vm_interpreted_calls_per_era", "exec_straggler_share"):
        assert value == 0.0
    elif name == "exec_largest_lane_share":
        assert value == 100.0  # one address in every tx.to: one lane a block
    else:
        assert value > 0.0


def test_calls_per_era_are_the_blocks_calls(traced_line):
    """One execution a committed call in validator 0 (the counter reading is
    high by the era that straddles the window's end, never by a factor)."""
    m = traced_line["metrics"]
    calls, writes = m["vm_calls_per_era"]["value"], m["contract_storage_writes_per_era"]["value"]
    assert 0 < calls <= 2 * SMALL["txs_per_block"]
    assert 0.8 < writes / calls < 1.7  # 1.25 a call at the mix's weights


def test_a_flipped_word_in_one_store_is_incorrect(monkeypatch):
    from perfbench.drivers import peers_smallbank

    real = peers_smallbank._smallbank_report

    def flipped(node, height, asked):  # validator 0's store, as check() reads it
        report = real(node, height, asked)
        report["words"][0][1] ^= 1
        return report

    monkeypatch.setattr(peers_smallbank, "_smallbank_report", flipped)
    line = run_cell(CELL, 2147489012, 2.0, False, time.monotonic(), rehearsal=Rehearsal(config=TINY))
    assert line["correct"] is False and line["failed"] == 0


def test_a_wrong_getbalance_receipt_is_incorrect(monkeypatch):
    from perfbench.drivers import peers_smallbank

    real = peers_smallbank._smallbank_report

    def wrong(node, height, asked):
        report = real(node, height, asked)
        report["returns"][-1] += 1
        return report

    monkeypatch.setattr(peers_smallbank, "_smallbank_report", wrong)
    line = run_cell(CELL, 2147489013, 2.0, False, time.monotonic(), rehearsal=Rehearsal(config=TINY))
    assert line["correct"] is False
