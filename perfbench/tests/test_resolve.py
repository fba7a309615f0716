"""BENCHMARK.json against the contract's own rules, every name resolving to
its files, and a configuration, a traffic mix, a driver and a per-layer
metric each added as new files plus entries — with no code changed."""
import importlib
import json
import re
import shutil

import pytest

from perfbench import layers, reductions, spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_keys_and_limits_of_the_contract():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert BENCH["paths"] == ["perfbench"]
    assert len(BENCH["command"]) <= 32
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert 2 <= len(BENCH["workloads"]) <= 24 and 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16 and 1 <= len(BENCH["per_layer"]) <= 128
    names = [
        x["name"]
        for key in ("configs", "workloads", "end_to_end", "per_layer")
        for x in BENCH[key]
    ]
    assert len(names) == len(set(names)), "a name is used once"
    assert all(NAME.match(n) for n in names)
    assert all(len(x["why"]) <= 200 for x in BENCH["configs"] + BENCH["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert four <= max(len(BENCH["workloads"]) // 2, 1)
    assert {c["name"] for c in BENCH["configs"]} == {w["config"] for w in BENCH["workloads"]}
    assert len(json.dumps(BENCH)) < 64 << 10


def test_metrics_of_the_contract():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25 and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert "bound" not in m and m["source"] in spec.SOURCES
        assert m["moves"] in e2e


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_to_files(workload):
    cell = spec.load_cell(workload)
    assert callable(cell.driver().Driver)
    assert cell.traffic["loop"] in ("open", "closed")
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in reported, "reported only where the metric it moves is"
        assert m["reduction"] in reductions.REDUCTIONS
        kind = m["read"]["kind"]
        assert kind == "bench" or layers.KIND_SOURCE[kind] == m["source"]


def test_config_files_state_their_source_and_cuts():
    for entry in BENCH["configs"]:
        with open(spec.ROOT / entry["file"], encoding="utf-8") as fh:
            cfg = json.load(fh)
        assert entry["file"].startswith("perfbench/")
        assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
        assert cfg["guarantees"] and cfg["assumed"]
        for forbidden in ("n", "f", "txs_per_block"):  # the deployment's shape
            assert forbidden not in cfg["reduced"]


def test_new_files_add_a_cell_with_no_code_change(tmp_path):
    """A later PR's move: a configuration, a mix, a driver and a per-layer
    metric arrive as new files and entries; the harness runs the new cell."""
    from perfbench import drivers
    from perfbench.harness import Rehearsal, run_cell

    bench_dir = tmp_path / "perfbench"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(spec.BENCH_DIR / sub, bench_dir / sub)
    config = json.loads((bench_dir / "configs" / "hb64-sim.json").read_text())
    config.update(
        name="hb4-new", driver="replay_new", n=4, f=1, txs_per_block=20,
        warm={"era_shapes": [4], "g2_msm_points": [2], "rs_payload_bytes": [64]},
        trace={"plane_prefix": "/device:TPU:", "op_lines": ["XLA Ops"], "busy_lines": ["XLA Ops"]},
    )
    (bench_dir / "configs" / "hb4-new.json").write_text(json.dumps(config))
    mix = json.loads((bench_dir / "traffic" / "quiet.json").read_text())
    mix.update(name="drip", rate_per_s=40)
    (bench_dir / "traffic" / "drip.json").write_text(json.dumps(mix))
    metric = {
        "name": "propose_p50_ms", "layer": "consensus_engine", "unit": "ms",
        "better": "lower", "moves": "era_p50_s", "source": "program_span",
        "read": {"kind": "span", "names": ["consensus.propose"]},
        "reduction": "p50", "scale": 1000,
    }
    (bench_dir / "layer_metrics" / "propose_p50_ms.json").write_text(json.dumps(metric))
    plugin = tmp_path / "new_drivers"
    plugin.mkdir()
    (plugin / "replay_new.py").write_text(
        "from perfbench.drivers.devnet import Driver  # a later PR's own code\n"
    )
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(
        {"name": "hb4-new", "source": config["source"], "reduced": [],
         "file": "perfbench/configs/hb4-new.json", "why": "a later PR's"}
    )
    bench["workloads"].append(
        {"name": "hb4.drip", "config": "hb4-new", "traffic": "drip", "chips": 1, "why": "new"}
    )
    bench["per_layer"].append(
        {k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
    )
    for m in bench["end_to_end"]:
        if m["name"] == "commit_p50_s":
            m["workloads"].append("hb4.drip")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    drivers.__path__.append(str(plugin))
    try:
        importlib.invalidate_caches()
        where = Rehearsal(root=tmp_path, bench_dir=bench_dir)
        import time

        line = run_cell("hb4.drip", 5, 2.0, False, time.monotonic(), rehearsal=where)
        assert line["correct"] and set(line["metrics"]) == {
            "era_p50_s", "commit_p50_s", "setup_s",
        }
        line = run_cell("hb4.drip", 5, 2.0, True, time.monotonic(), rehearsal=where)
        assert line["correct"] and line["metrics"]["propose_p50_ms"]["value"] > 0
    finally:
        drivers.__path__.remove(str(plugin))
