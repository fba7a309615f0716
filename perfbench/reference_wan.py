"""The plain reference of a shaped deployment's links, and the comparison
that holds a run to them.

Read from the configuration's `network` group alone (`regions`, the striping
rule "validator i is in regions[i % len(regions)]", `one_way_ms` by ordered
region pair, `jitter_burst`), never from the program's LinkShaper or its
spec string: which region a validator is in, and how long a frame from one
validator to another is held at least and at most.

The comparison: every process of the run reports how many frames its fault
session shaped and, for each of its peers, how many ping round trips its
RttTracker has seen and their smoothed time. A round trip crosses the link
once in each direction and each crossing is held by its sender's shaper, so
it can be no shorter than the two base delays; flush intervals and handling
only add. The tolerance is one-sided and zero.
"""
from __future__ import annotations

from typing import Dict, List, Sequence


class WanReference:
    def __init__(self, network: dict):
        self.regions: Sequence[str] = list(network["regions"])
        self.one_way_ms: Dict[str, Sequence[float]] = dict(network["one_way_ms"])
        self.burst_times = float(network["jitter_burst"]["times"])

    def region_of(self, validator: int) -> str:
        return self.regions[validator % len(self.regions)]

    def _link(self, src: int, dst: int) -> Sequence[float]:
        if src == dst:
            raise ValueError("a validator has no link to itself")
        return self.one_way_ms[f"{self.region_of(src)}-{self.region_of(dst)}"]

    def base_one_way(self, src: int, dst: int) -> float:
        """Seconds every frame src -> dst is held at least."""
        return self._link(src, dst)[0] / 1000.0

    def jitter_bound(self, src: int, dst: int) -> float:
        """Seconds beyond the base a frame src -> dst is held at most: the
        jitter, times the burst's factor."""
        return self._link(src, dst)[1] / 1000.0 * self.burst_times

    def round_trip_floor(self, a: int, b: int) -> float:
        return self.base_one_way(a, b) + self.base_one_way(b, a)


def check_links(ref: WanReference, reports: List[dict]) -> List[str]:
    """reports[i] is validator i's: {"shaped": frames its session shaped,
    "rtt": [[peer index, samples, smoothed seconds or None], ...]}. Returns
    what is wrong, each link by name; empty when every process shaped frames
    and every one of its n-1 round trips is at least its floor."""
    wrong: List[str] = []
    n = len(reports)
    for i, report in enumerate(reports):
        if report["shaped"] <= 0:
            wrong.append(
                f"validator {i}: its process shaped no frame, so none of its links "
                f"{i}->{{{','.join(str(j) for j in range(n) if j != i)}}} is held"
            )
        seen = {int(j): (int(samples), srtt) for j, samples, srtt in report["rtt"]}
        for j in range(n):
            if j == i:
                continue
            samples, srtt = seen.get(j, (0, None))
            floor = ref.round_trip_floor(i, j)
            if samples < 1 or srtt is None:
                wrong.append(f"link {i}<->{j}: validator {i} holds no round trip to {j}")
            elif srtt < floor:
                wrong.append(
                    f"link {i}<->{j} ({ref.region_of(i)}-{ref.region_of(j)}): validator "
                    f"{i}'s smoothed round trip {srtt * 1e3:.3f} ms is under the stated "
                    f"{floor * 1e3:.0f} ms of the two one-way delays"
                )
    return wrong
