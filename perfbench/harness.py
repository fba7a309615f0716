"""One run of one cell: stand the deployment up through its driver, warm it,
measure for --seconds, drain, decide `correct`, and reduce what was observed
to the cell's metrics. The drivers (perfbench/drivers/) know how a
deployment is stood up and loaded; the arithmetic is here and is the same
for every cell.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

from . import layers, xtrace
from . import reductions as R
from .proxy import BackendProxy
from .spec import BENCH_DIR, ROOT, Cell, load_cell
from .traffic import Record, Traffic

COMPILE_COUNTER = "device_compile_requests_total"
CACHE_HIT_COUNTER = "device_compile_cache_hits_total"


@dataclass
class Rehearsal:
    """Only the rehearsal tests pass this: the CPU is allowed and the
    configuration is shrunk. The command line cannot make one."""

    config: dict = field(default_factory=dict)
    traffic: dict = field(default_factory=dict)
    # another BENCHMARK.json and other data files than the repo's own
    root: Path = ROOT
    bench_dir: Path = BENCH_DIR


class Bench:
    """What a driver gets from the harness."""

    def __init__(self, cell: Cell, proxy: BackendProxy, rundir: str):
        self.cell = cell
        self.proxy = proxy
        self.rundir = rundir
        self.record = Record()
        self.values: Dict[str, List[float]] = {}  # layers' `bench` readings
        self.traffic = Traffic(
            cell.traffic,
            cell.seed,
            int(cell.config["chain_id"]),
            int(cell.config["txs_per_block"]),
        )
        self._slice_start: Optional[float] = None
        self._slice_dir: Optional[str] = None

    def say(self, msg: str) -> None:
        print(f"[perfbench {self.cell.name}] {msg}", file=sys.stderr, flush=True)

    def note(self, name: str, value: float) -> None:
        self.values.setdefault(name, []).append(float(value))

    # -- the profiled slice of a traced run -------------------------------------
    def start_slice(self, tag: str = "window") -> None:
        """tag: "window" for a slice of the measured window, "replay" for
        the kept batch's device pass after it."""
        import jax

        self._slice_tag = tag

        self._slice_dir = os.path.join(
            self.rundir, f"trace{len(self.record.slices)}"
        )
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans come from the tracer
        jax.profiler.start_trace(self._slice_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(
            xtrace.SYNC_NAME, mono_ns=time.monotonic_ns()
        ):
            pass
        self._slice_start = time.monotonic()

    def stop_slice(self) -> None:
        import jax

        end = time.monotonic()
        jax.profiler.stop_trace()
        paths = glob.glob(
            os.path.join(self._slice_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
        if len(paths) != 1:
            raise RuntimeError(f"profiler left {len(paths)} traces, not one")
        self.record.slices.append((self._slice_start, end, paths[0], self._slice_tag))
        self._slice_start = None

    @property
    def slicing(self) -> bool:
        return self._slice_start is not None


def _device_gate(cell: Cell, peaks: dict) -> dict:
    """The device as jax reports it; refuses anything but the chips the cell
    asks for, of a kind the peaks table knows."""
    from lachain_tpu.crypto import provider

    if cell.rehearsal is None:
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
            raise SystemExit("perfbench: JAX_PLATFORMS=cpu — no accelerator to measure")
    provider.open_device()
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if cell.rehearsal is None:
        if device["platform"] != "tpu":
            raise SystemExit(f"perfbench: no TPU (jax found {device['platform']})")
        if device["kind"] not in peaks:
            raise SystemExit(
                f"perfbench: device kind {device['kind']!r} is not in peaks.json"
            )
        if device["count"] < cell.chips:
            raise SystemExit(
                f"perfbench: cell needs {cell.chips} chips, jax found {device['count']}"
            )
    return device


def _make_backend(spec: dict):
    from lachain_tpu.crypto.native_backend import NativeBackend

    if spec["name"] != "tpu":
        raise ValueError(f"config backend {spec['name']!r}: only 'tpu' holds the chip")
    from lachain_tpu.crypto.tpu_backend import TpuBackend

    # min_device_lanes absent = the program's own routing rule
    return TpuBackend(
        host_backend=NativeBackend(), min_device_lanes=spec.get("min_device_lanes")
    )


def _memory_peak() -> int:
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()
    )


def end_to_end(record: Record) -> dict:
    """The client-side numbers. Only eras that complete inside the window
    count, and rates run to the end of the last complete era."""
    t0 = record.window_start
    counted = record.counted()
    if len(counted) < 2:
        raise RuntimeError(
            f"only {len(counted)} era(s) completed inside the window: no median"
        )
    ends = [b.t_commit for b in counted]
    eras = [b - a for a, b in zip([t0] + ends[:-1], ends)]
    commit_at = {h: b.t_commit for b in record.blocks for h in b.tx_hashes}
    attempted = record.attempted()
    refused = set(record.refused)
    failed = [h for h in attempted if h in refused or h not in commit_at]
    latencies = [
        commit_at[h] - record.due[h] for h in attempted if h in commit_at
    ]
    out = {
        "era_ends": ends,
        "eras": eras,
        "era_p50_s": R.percentile(eras, 50),
        "tx_per_s": sum(len(b.tx_hashes) for b in counted) / (ends[-1] - t0),
        "attempted": len(attempted),
        "failed": len(failed),
        "late": [record.submitted[h] - record.due[h] for h in attempted],
    }
    if latencies:
        out["commit_p50_s"] = R.percentile(latencies, 50)
        out["commit_p95_s"] = R.percentile(latencies, 95)
    return out


def _counter_delta(before: dict, after: dict):
    def delta(name: str, labels: Optional[dict]) -> float:
        key = (name, tuple(sorted((labels or {}).items())))
        return after.get(key, 0.0) - before.get(key, 0.0)

    return delta


def _host_spans(spans: List[dict], proxy: BackendProxy):
    """(name, start, end) of what the host was doing: the program's spans
    that nest like a call stack, and the benchmark's own at the seam. Left
    out: protocol lifetimes, which overlap by the dozen, and wait.net, which
    each connection's reader task holds open while the thread does other
    work, so that it would name every stretch of a served node."""
    out = [
        (s["name"], s["start"], s["end"])
        for s in spans
        if s["cat"] not in ("protocol", "tx", "block", "watchdog")
        and s["name"] != "wait.net"
        and s["end"] is not None
        and s["end"] > s["start"]
    ]
    out += [(f"backend.{m}", a, b) for m, a, b, _n in proxy.calls]
    return out


def _replay_kept_batch(bench: Bench) -> None:
    """Traced runs: the kept era batch once through each pipeline, after the
    window — the pair ROADMAP D1's routing rule waits for. The device pass
    is a profiled slice of its own, so every cell's traced run holds device
    work even where the program routes the window's batches to the host."""
    from . import reference

    if bench.proxy.last_era_batch is None:
        raise RuntimeError("no TPKE era batch reached the backend")
    jobs, vks, _got = bench.proxy.last_era_batch
    bench.note("era_batch_kept_slots", len(jobs))
    device_side, host_side = reference.era_batch_sides(bench.proxy)
    device_side.tpke_era_verify_combine(jobs, vks)  # its shape compiles here
    bench.start_slice("replay")
    t = time.monotonic()
    device_side.tpke_era_verify_combine(jobs, vks)
    bench.note("era_batch_device_s", time.monotonic() - t)
    bench.stop_slice()
    t = time.monotonic()
    host_side.tpke_era_verify_combine(jobs, vks)
    bench.note("era_batch_host_s", time.monotonic() - t)


def _routing_failures(cell: Cell, proxy: BackendProxy, delta) -> List[str]:
    """Where the configuration routes every batch to the device, the window
    must show it: batches on the device route, none on the host route, no
    slot rejected, and the kept batch equal to HostEraPipeline's answer."""
    from . import reference

    if cell.config["backend"].get("min_device_lanes") is None:
        return []
    wrong = reference.check_kept_batch(proxy)
    if delta("crypto_tpu_era_route_total", {"path": "device"}) <= 0:
        wrong.append("no era batch took the device route inside the window")
    if delta("crypto_tpu_era_route_total", {"path": "host"}) > 0:
        wrong.append("an era batch took the host route inside the window")
    if delta("crypto_tpu_era_slots_rejected_total", None) > 0:
        wrong.append("the device's answer was rejected for a slot")
    return wrong


def _traced(cell: Cell, bench: Bench, device: dict, peaks: dict, e2e: dict, delta) -> dict:
    """What only a traced run has: the per-layer metrics, the device's busy
    seconds over the profiled slices, and the breakdown."""
    from lachain_tpu.utils import tracing

    record, proxy = bench.record, bench.proxy
    how = {**peaks.get(device["kind"], {}).get("trace", {}), **cell.config.get("trace", {})}
    ops: List[xtrace.Op] = []
    for _lo, _hi, path, _tag in record.slices:
        ops += xtrace.device_ops(
            xtrace.dump_xplane(path), how["plane_prefix"], how["op_lines"]
        )
    by_tag = {
        tag: [(lo, hi) for lo, hi, _p, t in record.slices if t == tag]
        for tag in ("window", "replay")
    }
    slices = by_tag["window"] + by_tag["replay"]
    busy_ops = [op for op in ops if op.line in how["busy_lines"]]

    def busy_in(spans) -> float:
        return sum(xtrace.busy_seconds(busy_ops, lo, hi) for lo, hi in spans)

    # the line's device.busy_s covers every profiled slice, the replayed
    # batch included; the idle share is the window's slice alone
    busy_s = busy_in(slices)
    if cell.rehearsal is None and busy_s <= 0:
        raise RuntimeError("no operation ran on the device in the traced slices")
    device["busy_s"] = busy_s
    device["window_s"] = sum(hi - lo for lo, hi in slices)
    in_window = sum(hi - lo for lo, hi in by_tag["window"])
    bench.values["device_idle_share"] = [1.0 - busy_in(by_tag["window"]) / in_window]
    bench.values["peak_hbm_bytes"] = [device["memory_peak_bytes"]]
    bench.values["generator_late_s"] = e2e["late"]
    spans = tracing.snapshot()
    obs = layers.Observations(
        window=(record.window_start, e2e["era_ends"][-1]),
        era_ends=e2e["era_ends"],
        spans=spans,
        counter_delta=delta,
        proxy_calls=proxy.calls,
        ops=ops,
        slices=by_tag,
        bench=bench.values,
    )
    metrics = {}
    for m in cell.per_layer:
        value = layers.evaluate(m, obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    hosts = _host_spans(spans, proxy)
    idle: Dict[str, float] = {}
    for lo, hi in slices:
        for name, secs in xtrace.idle_by_host_span(busy_ops, hosts, lo, hi).items():
            idle[name] = idle.get(name, 0.0) + secs
    breakdown = {
        "device_ops": xtrace.top_ops(busy_ops),
        "idle_gaps": [
            [n, s] for n, s in sorted(idle.items(), key=lambda kv: -kv[1])[:10]
        ],
    }
    return {"metrics": metrics, "breakdown": breakdown}


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_process_start: float,
    rehearsal: Optional[Rehearsal] = None,
) -> dict:
    """Runs the cell and returns the object of the last line."""
    where = rehearsal or Rehearsal()
    cell = load_cell(workload, where.root, where.bench_dir)
    cell.seed, cell.trace, cell.rehearsal = seed, trace, rehearsal
    if rehearsal is not None:
        cell.config = {**cell.config, **rehearsal.config}
        cell.traffic = {**cell.traffic, **rehearsal.traffic}
    with open(BENCH_DIR / "peaks.json", encoding="utf-8") as fh:
        peaks = json.load(fh)["devices"]
    device = _device_gate(cell, peaks)

    from lachain_tpu.crypto import provider
    from lachain_tpu.utils import metrics, tracing

    def since_start() -> float:
        return time.monotonic() - t_process_start

    rundir = str(ROOT / ".perfbench_run" / str(os.getpid()))
    os.makedirs(rundir)
    proxy = BackendProxy(_make_backend(cell.config["backend"]), timed=trace)
    provider.set_backend(proxy)
    if trace:
        # the tracer is always on; a traced run only keeps the ring from
        # evicting what the window records
        tracing.set_capacity(1 << 19)
    bench = Bench(cell, proxy, rundir)
    record = bench.record
    driver = cell.driver().Driver(cell, bench)
    try:
        try:
            bench.say(f"device open after {since_start():.1f} s")
            driver.setup()
            bench.say(f"deployment up after {since_start():.1f} s")
            driver.warm()
            compiles_before = metrics.counter_value(COMPILE_COUNTER)
            bench.say(
                f"warm after {since_start():.1f} s; {compiles_before:.0f} programs "
                f"built, {metrics.counter_value(CACHE_HIT_COUNTER):.0f} from the "
                f"compile cache"
            )
            counters_before = metrics.counters_with_prefix("")
            driver.run_window(seconds)
            delta = _counter_delta(counters_before, metrics.counters_with_prefix(""))
            compiled = metrics.counter_value(COMPILE_COUNTER) - compiles_before
            driver.drain()
            failures = list(driver.check())
        finally:
            try:
                driver.close()
            finally:
                bench.traffic.close()
        failures += _routing_failures(cell, proxy, delta)
        if compiled:
            failures.append(f"{compiled:.0f} program(s) compiled inside the window")
        e2e = end_to_end(record)
        setup_s = record.window_start - t_process_start
        bench.say(
            f"setup {setup_s:.1f} s; {len(e2e['eras'])} eras in the window, "
            f"p50 {e2e['era_p50_s']:.3f} s; {e2e['tx_per_s']:.1f} tx/s; "
            f"attempted {e2e['attempted']}, failed {e2e['failed']}"
        )
        bench.say(f"backend calls by size: {proxy.sizes}")
        for f in failures:
            bench.say(f"NOT CORRECT: {f}")
        result = {
            "correct": not failures,
            "attempted": e2e["attempted"],
            "failed": e2e["failed"],
            "metrics": {},
            "device": device,
        }
        if trace:
            _replay_kept_batch(bench)
            device["memory_peak_bytes"] = _memory_peak()
            result.update(_traced(cell, bench, device, peaks, e2e, delta))
            return result
        device["memory_peak_bytes"] = _memory_peak()
        values = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise RuntimeError(f"no reading for {m['name']} in this run")
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        return result
    finally:
        provider.set_backend(None)  # the next caller builds its own
        shutil.rmtree(rundir, ignore_errors=True)
