"""The per-layer metrics that read the loop thread's ledger (PR 36:
node_loop_seconds_total{part}, consensus_engine_dispatch_seconds_total{family}
as the Python engine writes it, network_frames_total{dir},
network_flush_wait_seconds_total, node_loop_scopes_total): each has its file,
names a layer BENCHMARK.json knew, and gives a number on a traced N=4
rehearsal of hb7.quiet on the CPU. Counts and identities only: a CPU run says
nothing about time."""
import json
import time

import pytest

from perfbench import layers, reductions, spec
from perfbench.harness import Rehearsal, run_cell
from perfbench.tests.test_rehearsal import TINY

BENCH = spec.load_benchmark()
SERVED = ["hb7.full", "hb7.quiet", "hb16-wan.quiet", "hb7-smallbank.full"]
FULL = ["hb7.full", "hb7-smallbank.full"]
COUNTERS = {
    "node_loop_seconds_total", "consensus_engine_dispatch_seconds_total",
    "network_frames_total", "network_flush_wait_seconds_total", "node_loop_scopes_total",
}
NEW = [
    m for m in BENCH["per_layer"]
    if spec.load_layer_metric(m["name"])["read"].get("name") in COUNTERS
    and "hb64.full" not in m["workloads"]
]
OLD_LAYERS = {
    m["layer"] for m in BENCH["per_layer"][: BENCH["per_layer"].index(NEW[0])]
}


def test_nineteen_entries_appended_after_what_the_benchmark_had():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(NEW) == 19 and names[-19:] == [m["name"] for m in NEW]


@pytest.mark.parametrize("entry", NEW, ids=lambda m: m["name"])
def test_entry_has_its_file_and_a_layer_the_benchmark_knows(entry):
    metric = spec.load_layer_metric(entry["name"])
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert metric[key] == entry[key], key
    assert entry["layer"] in OLD_LAYERS
    assert entry["moves"] == "era_p50_s" and entry["better"] == "lower"
    assert entry["source"] == "program_counter" == layers.KIND_SOURCE[metric["read"]["kind"]]
    assert metric["reduction"] == "per_era" in reductions.REDUCTIONS
    assert "1/k" in metric["why"], "a counter reading is high by 1/k: say so"
    assert entry["workloads"] == (FULL if entry["layer"] == "tx_pool" else SERVED)


@pytest.fixture(scope="module")
def quiet_line():
    line = run_cell(
        "hb7.quiet", 11, 3.0, True, time.monotonic(), rehearsal=Rehearsal(config=TINY)
    )
    return json.loads(json.dumps(line))


@pytest.mark.parametrize(
    "entry", [m for m in NEW if "hb7.quiet" in m["workloads"]], ids=lambda m: m["name"]
)
def test_metric_is_a_number_on_a_rehearsal_of_hb7_quiet(quiet_line, entry):
    value = quiet_line["metrics"][entry["name"]]
    assert value["unit"] == entry["unit"] and value["value"] >= 0.0
    # every part and family of a served era has work in it; only the journal
    # and the pool are beside the point at this size
    assert value["value"] > 0.0


def test_the_parts_fill_the_era_on_the_rehearsal(quiet_line):
    """What the counters can show of the partition: idle + other + the
    families + the frame parts are most of the era (a traced line carries
    it as engine_self_s_per_era: the `era` span less execution), and no more
    than the era and the straddling one (a counter reading is high by up to
    1/k)."""
    m = {k: v["value"] for k, v in quiet_line["metrics"].items()}
    named = sum(
        m[e["name"]] for e in NEW
        if e["unit"] == "s" and e["name"] in m and e["name"] != "net_flush_wait_s_per_era"
    )
    era = m["engine_self_s_per_era"]
    assert 0.6 * era < named < 2.0 * era, (named, era)
