#!/usr/bin/env python3
"""The benchmark's one command: runs one cell once, in this process.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object: correct, attempted, failed,
metrics (the cell's end-to-end metrics, or with --trace 1 its per-layer
metrics), device, and with --trace 1 breakdown. Everything said on the way
goes to stderr. Without a TPU of a kind peaks.json knows it exits non-zero
and prints no result; there is no switch that lets it measure a CPU.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the contract's limits: 360 s a run, 1200 s where the checkout still compiles
RUN_LIMIT_S, FIRST_RUN_LIMIT_S = 360, 1200


def _give_up(signum, _frame):
    # raised, not the default action, so that every finally runs and the
    # drivers stop the processes they started
    raise TimeoutError(f"perfbench: signal {signum}: the run exceeded its limit or was stopped")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from lachain_tpu.crypto import provider
    from perfbench.harness import run_cell

    # a hang is a failure, not a timeout: the whole run sits under an alarm.
    # A marker beside the compile cache says this cell has compiled here.
    marker = os.path.join(
        provider.compile_cache_dir(), f".perfbench-{args.workload}-{args.trace}"
    )
    signal.signal(signal.SIGALRM, _give_up)
    signal.signal(signal.SIGTERM, _give_up)
    signal.alarm(
        (RUN_LIMIT_S if os.path.exists(marker) else FIRST_RUN_LIMIT_S) - 15
    )
    result = run_cell(
        args.workload,
        args.seed,
        args.seconds,
        bool(args.trace),
        t_process_start=T_PROCESS_START,
    )
    signal.alarm(0)
    os.makedirs(os.path.dirname(marker), exist_ok=True)
    open(marker, "w").close()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
