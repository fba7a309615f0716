"""Every cell of BENCHMARK.json end to end on the CPU at a tiny committee
(N=4, 20-transaction blocks, 3 s): the last line's keys and `correct`. The
harness takes a Rehearsal only from here; the command line cannot make one
and refuses to measure a CPU."""
import json
import os
import subprocess
import sys
import time

import pytest

from perfbench import spec
from perfbench.harness import Rehearsal, run_cell

BENCH = spec.load_benchmark()
TINY = {
    "n": 4,
    "f": 1,
    "txs_per_block": 20,
    "warm": {"era_shapes": [4], "g2_msm_points": [2], "rs_payload_bytes": [64], "heights": 2},
    "profile": {"skip_eras": 1, "skip_seconds": 0.5, "seconds": 1.0},
    # the CPU is in no peaks table: say where a trace would hold device ops
    "trace": {"plane_prefix": "/device:TPU:", "op_lines": ["XLA Ops"], "busy_lines": ["XLA Ops"]},
}


def _applies(metric, workload):
    return workload in metric.get("workloads", [workload])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_end_to_end_on_the_cpu(workload, trace):
    line = run_cell(
        workload, 7, 3.0, bool(trace), time.monotonic(), rehearsal=Rehearsal(config=TINY)
    )
    line = json.loads(json.dumps(line))  # what the last line would carry
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"} | (
        {"breakdown"} if trace else set()
    )
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"} and isinstance(value["value"], float)
    if not trace:
        want = {m["name"] for m in BENCH["end_to_end"] if _applies(m, workload)}
        assert set(line["metrics"]) == want
        assert all(v["value"] > 0 for v in line["metrics"].values())
        return
    allowed = {m["name"] for m in BENCH["per_layer"] if _applies(m, workload)}
    assert set(line["metrics"]) <= allowed and len(line["metrics"]) >= 5
    assert line["device"]["window_s"] > 0 and line["device"]["busy_s"] >= 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in line["breakdown"].values())
    assert not os.listdir(spec.ROOT / ".perfbench_run"), "the run removes what it wrote"


def test_command_refuses_to_measure_a_cpu():
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "hb7.quiet", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no accelerator" in proc.stderr


def test_peer_that_exits_early_fails_the_run_and_leaves_nothing(monkeypatch):
    """A child that dies is an error with its stderr shown, never a hang;
    the other children are gone afterwards."""
    from perfbench.drivers import peers

    real_spawn = peers.Driver._spawn
    started = []

    def spawn(self, index):
        child = real_spawn(self, index)
        started.append(child.proc)
        if index == 2:
            child.proc.kill()
        return child

    monkeypatch.setattr(peers.Driver, "_spawn", spawn)
    with pytest.raises(RuntimeError, match="validator 2 .*(exited early|is gone)"):
        run_cell("hb7.quiet", 7, 1.0, False, time.monotonic(), rehearsal=Rehearsal(config=TINY))
    assert len(started) >= 2  # the failure may come before the last spawn
    assert all(proc.poll() is not None for proc in started), "no child outlives the run"
    assert not os.listdir(spec.ROOT / ".perfbench_run")
