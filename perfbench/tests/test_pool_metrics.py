"""The per-layer metrics of the transaction pool (`pool.peek`,
`pool.remove_included`, `pool.sanitize`, `devnet.submit_tx`,
txpool_state_nonce_reads_total): each entry of BENCHMARK.json has its file
and a source its reader accepts, gives a value on a traced N=4 rehearsal on
the CPU, and reads nothing from a program that lacks the spans. Counts only:
a CPU run says nothing about time."""
import json
import time

import pytest

from perfbench import layers, reductions, spec
from perfbench.harness import Rehearsal, run_cell
from perfbench.tests.test_rehearsal import TINY

BENCH = spec.load_benchmark()
ALL = ["hb64.full", "hb64.quiet", "hb7.full"]
# the in-process devnet alone hands load over through Devnet.submit_tx
CELLS = {
    "pool_peek_s_per_era": ALL,
    "pool_evict_s_per_era": ALL,
    "pool_submit_s_per_era": ALL[:2],
    "pool_nonce_reads_per_era": ALL,
}
NEW = [m for m in BENCH["per_layer"] if m["layer"] == "tx_pool"]


def test_the_entries_are_the_ones_the_issue_lists():
    assert [m["name"] for m in NEW] == list(CELLS)
    # appended as one block after what the benchmark had (not "the last
    # entries": the next PR appends after these)
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("pool_peek_s_per_era")
    assert names[first:first + len(NEW)] == list(CELLS)
    assert names.index("cross_root_sign_s_per_era") == first - 1


@pytest.mark.parametrize("entry", NEW, ids=lambda m: m["name"])
def test_entry_has_its_file_and_a_source_its_reader_accepts(entry):
    assert entry["workloads"] == CELLS[entry["name"]]
    assert entry["moves"] == "era_p50_s" and entry["better"] == "lower"
    metric = spec.load_layer_metric(entry["name"])
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert metric[key] == entry[key], key
    assert metric["reduction"] == "per_era" in reductions.REDUCTIONS
    kind = metric["read"]["kind"]
    assert layers.KIND_SOURCE[kind] == metric["source"]
    assert len(metric["why"]) > 40
    if kind == "counter":
        assert metric["read"] == {"kind": "counter", "name": "txpool_state_nonce_reads_total"}
        assert "1/k" in metric["why"], "a counter reading is high by 1/k: say so"
    else:
        assert all(n.startswith(("pool.", "devnet.")) for n in metric["read"]["names"])


@pytest.fixture(scope="module")
def traced_lines():
    return {
        cell: json.loads(
            json.dumps(
                run_cell(cell, 13, 3.0, True, time.monotonic(), rehearsal=Rehearsal(config=TINY))
            )
        )
        for cell in ("hb64.full", "hb7.full")
    }


@pytest.mark.parametrize("cell", ["hb64.full", "hb7.full"])
@pytest.mark.parametrize("entry", NEW, ids=lambda m: m["name"])
def test_entry_gives_a_value_on_a_traced_rehearsal(traced_lines, entry, cell):
    line = traced_lines[cell]
    assert line["correct"] is True
    if cell not in entry["workloads"]:
        assert entry["name"] not in line["metrics"]
        return
    got = line["metrics"][entry["name"]]
    assert got["unit"] == entry["unit"] and got["value"] > 0.0


def test_the_readings_agree_with_each_other(traced_lines):
    value = {k: v["value"] for k, v in traced_lines["hb64.full"]["metrics"].items()}
    # the pool's calls lie inside the callbacks that make them
    assert value["pool_peek_s_per_era"] < value["cross_root_input_s_per_era"]
    assert value["pool_evict_s_per_era"] < value["cross_root_produce_s_per_era"]
    # the memo bounds the reads by senders, whatever was added: a validator
    # reads a sender once after a commit and once if it was not pooled then;
    # the counter reading is high by 1/k, at most twice
    accounts = spec.load_cell("hb64.full").traffic["accounts"]
    assert value["pool_nonce_reads_per_era"] <= 2 * (2 * TINY["n"] * accounts)


def _observations(spans, reads):
    return layers.Observations(
        window=(0.0, 10.0),
        era_ends=[4.0, 8.0],
        spans=spans,
        counter_delta=lambda name, labels: reads if name == "txpool_state_nonce_reads_total" else 0.0,
    )


def _span(name, start, end):
    return {"name": name, "cat": "pool", "start": start, "end": end, "open": False, "args": {}}


def test_readers_reduce_the_spans_and_read_nothing_without_them():
    metric = {m["name"]: {**spec.load_layer_metric(m["name"]), **m} for m in NEW}
    spans = [
        _span("era", 0.5, 4.0), _span("cross.root_input", 0.5, 1.5), _span("pool.peek", 0.6, 1.0),
        _span("pool.remove_included", 3.0, 3.25), _span("pool.sanitize", 3.25, 3.5),
        _span("devnet.submit_tx", 4.1, 4.6), _span("pool.peek", 5.0, 5.2),
        _span("pool.peek", 11.0, 12.0),  # starts after the last counted era: not the window's
    ]
    with_spans = _observations(spans, reads=300.0)
    assert layers.evaluate(metric["pool_peek_s_per_era"], with_spans) == pytest.approx(0.3)
    assert layers.evaluate(metric["pool_evict_s_per_era"], with_spans) == pytest.approx(0.25)
    assert layers.evaluate(metric["pool_submit_s_per_era"], with_spans) == pytest.approx(0.25)
    assert layers.evaluate(metric["pool_nonce_reads_per_era"], with_spans) == 150.0
    # the parent's program: the same snapshot without the pool's spans, no counter
    parent = _observations([s for s in spans if not s["name"].startswith(("pool.", "devnet."))], 0.0)
    for name in ("pool_peek_s_per_era", "pool_evict_s_per_era", "pool_submit_s_per_era"):
        assert layers.evaluate(metric[name], parent) is None
    assert layers.evaluate(metric["pool_nonce_reads_per_era"], parent) == 0.0
