"""Perf-regression gate: diff a bench results JSON against a baseline.

Usage: python benchmarks/compare.py BASELINE CURRENT [--min-threshold-pct P]

Both inputs accept any of the shapes the bench drivers emit:
  - a bare result object (one JSON line from bench.py /
    bench_consensus_sim.py),
  - a driver wrapper {"cmd", "rc", "tail", "parsed": {...}} (the parsed
    object is used),
  - a text file whose LAST line is the JSON result (bench stdout piped
    through tee), or "-" for stdin.

Comparison policy: the headline "value" is compared in the direction its
"metric" name implies (…_per_s → higher is better; …_s / …latency… →
lower is better), plus every shared latency side-channel field
(tpu_era_s, per_node_normalized_latency_s, …). The allowed delta per
field is max(--min-threshold-pct, baseline trial_spread_pct, current
trial_spread_pct) — the PR-4 noise fields, so a wide-spread run widens
its own gate instead of false-failing on noise.

Exit codes: 0 = within thresholds, 1 = regression, 2 = input/schema
error. Wired into `make bench-gate`.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional, Tuple

# latency-shaped side fields compared lower-is-better when both runs
# report them (the headline "value" is handled separately)
LATENCY_FIELDS = (
    "tpu_era_s",
    "tpu_host_s",
    "baseline_era_s",
    "per_node_normalized_latency_s",
    "fastsync_failover_recovery_s",
    # bench_storage_commit phase breakdown (PR 11): compared only when
    # both runs report them, so older baselines stay valid
    "exec_s",
    "merkle_hash_s",
    "merkle_assemble_s",
    "wal_fsync_s",
    # tx lifecycle e2e percentiles (PR 15, bench_consensus_sim via
    # utils/txtrace stamps): submit -> commit wall time of sampled txs,
    # interpolated from the tx_e2e_seconds histogram. Only compared when
    # both runs report them, so pre-15 baselines stay valid.
    "tx_e2e_p50_s",
    "tx_e2e_p99_s",
    # WAN survival curve (PR 18, bench_wan_sim): era commit p99 under the
    # steepest shaped RTT point, plus the observed SRTT itself — rtt_ms
    # rising means the shaper (or the real WAN) got slower, which would
    # otherwise masquerade as an era-latency regression. Only compared
    # when both runs report them, so pre-18 baselines stay valid.
    "era_latency_p99_s",
    "rtt_ms",
    # RBC batching (PR 20, bench_consensus_sim): the fastest era's RBC codec
    # phase (host + device RS time) and its idle remainder — the two columns
    # the batched Reed-Solomon engine and the flush overlap exist to shrink.
    # Only compared when both runs report them, so pre-20 baselines stay
    # valid.
    "rbc_s",
    "idle_s",
)

# throughput-shaped side fields compared higher-is-better when both runs
# report them (bench_storage_commit rows carry committed tx/s; the mesh
# bench rows carry the per-era device-utilization floor — a drop means the
# chips idled more of the era wall than the MULTICHIP baseline allows)
THROUGHPUT_FIELDS = ("tx_per_s_commit", "mesh_device_util_floor")


def load_result(path: str) -> dict:
    """File/stdin -> bare result dict (unwraps the driver envelope)."""
    text = sys.stdin.read() if path == "-" else open(path).read()
    text = text.strip()
    if not text:
        raise ValueError(f"{path}: empty input")
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        # bench stdout with warmup logs: the result is the last JSON line
        for line in reversed(text.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                obj = json.loads(line)
                break
        else:
            raise ValueError(f"{path}: no JSON object found")
    if not isinstance(obj, dict):
        raise ValueError(f"{path}: expected a JSON object")
    if "parsed" in obj and isinstance(obj["parsed"], dict):
        obj = obj["parsed"]
    if "metric" not in obj or "value" not in obj:
        raise ValueError(f"{path}: result lacks 'metric'/'value' fields")
    return obj


def higher_is_better(metric: str) -> bool:
    m = metric.lower()
    if "per_s" in m or "throughput" in m:
        return True
    if "latency" in m or m.endswith("_s") or "seconds" in m:
        return False
    return True  # default: treat the headline number as a score


def threshold_pct(base: dict, cur: dict, floor: float) -> float:
    return max(
        floor,
        float(base.get("trial_spread_pct") or 0.0),
        float(cur.get("trial_spread_pct") or 0.0),
    )


def check_field(
    name: str,
    base_v: float,
    cur_v: float,
    higher_better: bool,
    allowed_pct: float,
) -> Tuple[bool, float]:
    """-> (regressed, delta_pct). delta_pct > 0 means 'got worse'."""
    if base_v == 0:
        return False, 0.0
    if higher_better:
        delta = (base_v - cur_v) / base_v * 100.0
    else:
        delta = (cur_v - base_v) / base_v * 100.0
    return delta > allowed_pct, delta


def compare(base: dict, cur: dict, floor: float) -> Tuple[int, str]:
    if base["metric"] != cur["metric"]:
        return 2, (
            f"metric mismatch: baseline is {base['metric']!r}, "
            f"current is {cur['metric']!r}"
        )
    # mesh runs are only comparable against a baseline recorded on the
    # same mesh width — utilization and per-era walls both scale with it
    if (base.get("mesh_devices") or 0) != (cur.get("mesh_devices") or 0):
        return 2, (
            f"mesh_devices mismatch: baseline ran on "
            f"{base.get('mesh_devices') or 0} devices, current on "
            f"{cur.get('mesh_devices') or 0}"
        )
    allowed = threshold_pct(base, cur, floor)
    rows = []
    failed = False
    hb = higher_is_better(base["metric"])
    checks: list = [("value", hb)]
    checks += [
        (f, False)
        for f in LATENCY_FIELDS
        if f in base and f in cur and f != "baseline_era_s"
    ]
    checks += [
        (f, True) for f in THROUGHPUT_FIELDS if f in base and f in cur
    ]
    for field, field_hb in checks:
        try:
            bv, cv = float(base[field]), float(cur[field])
        except (TypeError, ValueError, KeyError):
            continue
        regressed, delta = check_field(field, bv, cv, field_hb, allowed)
        failed = failed or regressed
        rows.append(
            f"  {field:<32} {bv:>12.4f} -> {cv:>12.4f}  "
            f"{delta:+7.1f}% worse "
            f"(allowed {allowed:.1f}%) "
            f"{'REGRESSION' if regressed else 'ok'}"
        )
    # per-era flight-recorder walls (bench_consensus_sim
    # era_phase_report_s), era-by-era where both runs report the era:
    # catches a regression hiding in one era of a pipelined batch that
    # the batch-mean headline would smear away
    bper = base.get("era_phase_report_s") or {}
    cper = cur.get("era_phase_report_s") or {}
    for era in sorted(set(bper) & set(cper), key=str):
        try:
            bv = float(bper[era]["wall_s"])
            cv = float(cper[era]["wall_s"])
        except (TypeError, ValueError, KeyError):
            continue
        field = f"era[{era}].wall_s"
        regressed, delta = check_field(field, bv, cv, False, allowed)
        failed = failed or regressed
        rows.append(
            f"  {field:<32} {bv:>12.4f} -> {cv:>12.4f}  "
            f"{delta:+7.1f}% worse "
            f"(allowed {allowed:.1f}%) "
            f"{'REGRESSION' if regressed else 'ok'}"
        )
        # idle-opacity gate (ISSUE 16): the fraction of idle the recorder
        # could NOT attribute to a named wait bucket must not creep back
        # up. Fractions sit near zero, so a percent-relative check would
        # be all noise — gate on an absolute slack over the baseline
        # instead (the percent threshold re-used as percentage points).
        try:
            bfrac = float(bper[era]["idle_unattributed_fraction"])
            cfrac = float(cper[era]["idle_unattributed_fraction"])
        except (TypeError, ValueError, KeyError):
            continue  # pre-16 baseline: nothing to hold the line against
        slack = max(0.10, allowed / 100.0)
        frac_bad = cfrac > bfrac + slack
        failed = failed or frac_bad
        field = f"era[{era}].idle_unattr_frac"
        rows.append(
            f"  {field:<32} {bfrac:>12.4f} -> {cfrac:>12.4f}  "
            f"{(cfrac - bfrac) * 100.0:+7.1f}pp worse "
            f"(allowed {slack * 100.0:.1f}pp) "
            f"{'REGRESSION' if frac_bad else 'ok'}"
        )
    verdict = "REGRESSION" if failed else "PASS"
    header = (
        f"{verdict}: {base['metric']} vs baseline "
        f"(noise-derived threshold {allowed:.1f}%)"
    )
    return (1 if failed else 0), "\n".join([header] + rows)


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("baseline", help="baseline results JSON (or -)")
    ap.add_argument("current", help="current results JSON (or -)")
    ap.add_argument(
        "--min-threshold-pct",
        type=float,
        default=5.0,
        help="floor for the allowed delta when both runs report low "
        "trial_spread_pct (default 5%%)",
    )
    args = ap.parse_args(argv)
    try:
        base = load_result(args.baseline)
        cur = load_result(args.current)
    except (OSError, ValueError) as e:
        print(f"compare.py: {e}", file=sys.stderr)
        return 2
    try:
        rc, report = compare(base, cur, args.min_threshold_pct)
    except (KeyError, TypeError, ValueError) as e:
        print(f"compare.py: schema error: {e!r}", file=sys.stderr)
        return 2
    print(report)
    return rc


if __name__ == "__main__":
    sys.exit(main())
