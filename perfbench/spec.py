"""Everything the harness knows about a cell comes from data files found by
name: BENCHMARK.json names the cell's configuration and traffic mix and the
metrics; the configuration names its driver. No table of names lives in
code, so a later PR adds a cell, a mix, a driver or a per-layer metric as
new files plus entries."""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _read_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_benchmark(root: Path = ROOT) -> dict:
    return _read_json(Path(root) / "BENCHMARK.json")


def _applies(metric: dict, workload: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or workload in cells


@dataclass
class Cell:
    """One entry of `workloads`, resolved to its files."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]  # BENCHMARK.json entry merged over the metric's file
    seed: int = 0
    trace: bool = False
    # what only the rehearsal tests pass (harness.Rehearsal); None on the chip
    rehearsal: Optional[object] = None

    def driver(self):
        """The module perfbench/drivers/<config.driver>.py; it defines
        `Driver(cell, bench)` with setup / warm / run_window / drain / check /
        close."""
        return importlib.import_module(
            f"perfbench.drivers.{self.config['driver']}"
        )


def load_layer_metric(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _read_json(bench_dir / "layer_metrics" / f"{name}.json")


def load_cell(
    workload: str, root: Path = ROOT, bench_dir: Path = BENCH_DIR
) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(
            f"perfbench: no workload {workload!r} in BENCHMARK.json "
            f"(have: {', '.join(sorted(by_name))})"
        )
    entry = by_name[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _read_json(Path(root) / cfg_entry["file"])
    traffic = _read_json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    end_to_end = [m for m in bench["end_to_end"] if _applies(m, workload)]
    per_layer = []
    for m in bench["per_layer"]:
        if not _applies(m, workload):
            continue
        reader = load_layer_metric(m["name"], bench_dir)
        for key in ("unit", "layer", "moves"):
            if reader.get(key) != m.get(key):
                raise SystemExit(
                    f"perfbench: per-layer metric {m['name']}: {key} differs "
                    f"between BENCHMARK.json and its file"
                )
        per_layer.append({**reader, **m})
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=end_to_end,
        per_layer=per_layer,
    )
