"""Per-layer metrics that are a ratio of counters: the change of some program
counters over the window divided by the change of others. No reduction of
reductions.py divides one reading by another, so the metric's file says
which counters, beside the `bench` value it is read from:

    "read": {"kind": "bench", "name": "<metric>",
             "ratio": {"of": ["<counter>", ...], "over": ["<counter>", ...]}}

and the driver of a cell that lists such metrics calls `note` with the
registry (`metrics.counters_with_prefix("")`) as it stood before and after
its window. A counter's label sets are summed. Nothing is noted, and the
metric is left out of the line, where the program has none of the `of`
counters or the `over` counters did not move.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

Registry = Dict[Tuple[str, tuple], float]


def note(bench, per_layer: List[dict], before: Registry, after: Registry) -> None:
    def moved(names: Iterable[str]) -> float:
        return sum(
            v - before.get(key, 0.0) for key, v in after.items() if key[0] in names
        )

    have = {key[0] for key in after}
    for metric in per_layer:
        ratio = metric["read"].get("ratio")
        if ratio is None or not have & set(ratio["of"]):
            continue
        over = moved(ratio["over"])
        if over > 0:
            bench.note(metric["read"]["name"], moved(ratio["of"]) / over)
