"""The plain reference of a rolling restart, and the comparisons that hold a
run to it.

Written from the configuration file's words (`roll`, `n`, `f`) and the
seed alone; it imports nothing of the driver and nothing of the program but
its signature library. Two things are decided here:

* The schedule. From `roll` and the seed: who is killed, in which order,
  and when a kill is allowed. The driver's log of what it did (one entry a
  kill: victim, pids, t_kill, t_reaped, t_spawned, t_listening, t_rejoined,
  all on time.monotonic()) is checked against it: one victim at a time, in
  the stated order, never a validator the configuration exempts, no kill
  before `first_kill_s` or inside the window's last stretch, a new process
  only after the old one is gone, a kill only after the previous victim is
  back, and enough restarts completed inside the window.
* The quorum. Every committed header carries signatures of at least N-f
  distinct validators of the set that verify, by plain `ecdsa.verify_hash`
  calls, against the header's hash: a restarted validator's signature
  counts only if it is one, and a block committed while one was away still
  has its five.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

# how long "at once" may take on a loaded host: from the old process reaped
# to the new one spawned, and from a victim back to the next kill
AT_ONCE_S = 1.0


class RollReference:
    def __init__(self, roll: dict, n: int, seed: int):
        victims = roll["victims"]
        self.lo, self.hi = int(victims["from"]), int(victims["to"])
        self.never = {int(v) for v in victims["never"]}
        if not (0 <= self.lo <= self.hi < n) or self.never & set(
            range(self.lo, self.hi + 1)
        ):
            raise ValueError("roll.victims: not a range of validators that may die")
        # "1 + (seed mod 6)": the first victim; six is the size of the range
        self.count = self.hi - self.lo + 1
        self.first = self.lo + seed % self.count
        self.first_kill_s = float(roll["first_kill_s"])
        self.dwell_s = float(roll["dwell_s"])
        self.quiet_end_s = float(roll["at_window_end"]["no_kill_in_last_s"])
        self.min_restarts = int(roll["min_restarts"])
        if roll["signal"] != "SIGKILL" or roll["gate"] != "consensus":
            raise ValueError("roll: this reference knows SIGKILL and the consensus gate")

    def victim(self, k: int) -> int:
        """The k-th victim (k from 0): in turn, ascending, wrapping."""
        return self.lo + (self.first - self.lo + k) % self.count

    def may_kill(self, t: float, window_start: float, window_end: float) -> bool:
        return (
            window_start + self.first_kill_s <= t <= window_end - self.quiet_end_s
        )


def completed_in_window(log: Sequence[dict], window_end: float) -> int:
    return sum(
        1
        for e in log
        if e.get("t_rejoined") is not None and e["t_rejoined"] <= window_end
    )


def check_schedule(
    ref: RollReference,
    log: Sequence[dict],
    window_start: float,
    window_end: float,
    min_restarts: Optional[int] = None,
) -> List[str]:
    """What the driver's log breaks of the schedule; empty when it holds."""
    wrong: List[str] = []
    need = ref.min_restarts if min_restarts is None else min_restarts
    previous = None
    for k, e in enumerate(log):
        who = f"restart {k} (validator {e['victim']})"
        if e["victim"] in ref.never or not ref.lo <= e["victim"] <= ref.hi:
            wrong.append(f"{who}: a validator the configuration never kills")
        if e["victim"] != ref.victim(k):
            wrong.append(f"{who}: out of order, validator {ref.victim(k)} was due")
        if not ref.may_kill(e["t_kill"], window_start, window_end):
            wrong.append(
                f"{who}: killed {e['t_kill'] - window_start:.3f} s into a "
                f"{window_end - window_start:.0f} s window, outside "
                f"[{ref.first_kill_s}, end - {ref.quiet_end_s}]"
            )
        if not e["t_kill"] <= e["t_reaped"] <= e["t_spawned"]:
            wrong.append(f"{who}: the new process was spawned before the old one was reaped")
        if e["new_pid"] == e["old_pid"]:
            wrong.append(f"{who}: the same process, not a new one")
        if e["t_spawned"] - e["t_reaped"] > AT_ONCE_S:
            wrong.append(f"{who}: restarted {e['t_spawned'] - e['t_reaped']:.2f} s after the kill, not at once")
        if previous is not None:
            if previous.get("t_rejoined") is None:
                wrong.append(f"{who}: killed while validator {previous['victim']} was still out")
            else:
                gap = e["t_kill"] - previous["t_rejoined"]
                if gap < ref.dwell_s:
                    wrong.append(
                        f"{who}: killed {-gap:.3f} s before validator "
                        f"{previous['victim']} was back"
                    )
                elif gap > ref.dwell_s + AT_ONCE_S:
                    wrong.append(
                        f"{who}: killed {gap:.2f} s after validator "
                        f"{previous['victim']} was back; dwell_s is {ref.dwell_s}"
                    )
        if e.get("t_rejoined") is not None and not (
            e["t_spawned"] <= e["t_listening"] <= e["t_rejoined"]
        ):
            wrong.append(f"{who}: listening and rejoined are not in order")
        previous = e
    if log and log[-1].get("t_rejoined") is None:
        wrong.append(f"validator {log[-1]['victim']} never came back")
    done = completed_in_window(log, window_end)
    if done < need:
        wrong.append(f"{done} restart(s) completed inside the window, {need} needed")
    return wrong


def check_multisigs(
    n: int,
    f: int,
    validator_pubs: Sequence[bytes],
    headers: Iterable[Tuple[int, bytes, Sequence[Tuple[int, bytes]]]],
) -> List[str]:
    """headers: (height, header hash, [(validator index, signature), ...]).
    Each must hold at least n-f signatures of distinct validators of the
    set that verify against the hash."""
    from lachain_tpu.crypto import ecdsa

    wrong: List[str] = []
    for height, header_hash, signatures in headers:
        valid = set()
        for index, sig in signatures:
            if (
                0 <= index < n
                and index not in valid
                and ecdsa.verify_hash(validator_pubs[index], header_hash, sig)
            ):
                valid.add(index)
        if len(valid) < n - f:
            wrong.append(
                f"block {height}: {len(valid)} valid signature(s) of distinct "
                f"validators, {n - f} needed"
            )
    return wrong
