"""perfbench — the benchmark of lachain-tpu (BENCHMARK.json at the repo root).

One command runs one cell once in a new process:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one driver or
one per-layer metric is a file of its own, found by the name BENCHMARK.json
gives it (spec.py); the yardstick — traffic generation, reductions, the
profiler-trace reader, peaks.json, the plain references and the comparison
that decides `correct` — lives here and nowhere else. PERF.md at the repo
root says what each piece measures and why.
"""
