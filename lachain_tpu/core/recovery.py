"""The clock of one restart: a node opened on a store that already holds a
chain times its way back into the committee, phase by phase.

The phases follow one another without a gap, so their seconds add up to the
time from `listening` to `rejoined`:

  node.recover.connect   listening -> the first sync request (or a verified
                         frame from every peer, if that comes sooner: it
                         does not, a peer's worker dials the restarted node
                         again when its backoff runs out, and one peer's
                         answer to a height probe is enough to begin); args
                         `peers`, `seen` = how many had been heard by then
  node.recover.catch_up  -> the last block the synchronizer applied before
                         the node finished an era itself (args `blocks`,
                         `txs`); known in hindsight, so written at the
                         rejoin. Not "the best peer has nothing more": a
                         peer's worker delivers, with its first frame, the
                         answers it had queued for the process that died,
                         and a height from before the kill reads as level
  node.recover.rejoin    -> the first era `Node.run_era` finished with
                         outcome `consensus` (args `era`, `eras_synced`:
                         eras a synced block superseded since the restart,
                         `all_seen_s`: listening -> every peer heard)

Before them, once each and not contiguous with anything: `lsm.open`
(storage/lsm.py), `node.recover.pool`, `node.recover.journal` (core/node.py).
A node on a fresh store has no clock (`Node.recovery` is None): nothing
here runs in an era of a node that never went away.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional

from ..utils import tracing

PHASES = ("connect", "catch_up", "rejoin")


class RecoveryClock:
    def __init__(self, height_at_open: int):
        self.height_at_open = height_at_open
        # time.monotonic() marks: listening, then the end of each phase
        self.marks: Dict[str, float] = {}
        self.blocks = 0
        self.txs = 0
        self.eras_synced = 0
        self.rejoined_era: Optional[int] = None
        self._last_synced: Optional[float] = None
        self._all_seen: Optional[float] = None
        self._connect_sid = 0
        self._peers = 0
        self._seen = 0
        # called once, at the moment the rejoin phase ends (inside
        # run_era, before the loop goes on to the next era's proposal)
        self.on_rejoined: Optional[Callable[[], None]] = None

    @property
    def done(self) -> bool:
        return "rejoin" in self.marks

    # -- marks, in order ---------------------------------------------------
    def listening(self, peers: int) -> None:
        if "listening" in self.marks:
            return
        self._peers = peers
        self.marks["listening"] = time.monotonic()
        self._connect_sid = tracing.begin("node.recover.connect", cat="recover")

    def peer_seen(self, unseen: int) -> None:
        """A first verified frame from a peer; `unseen` are still silent."""
        self._seen = self._peers - unseen
        if not unseen:
            self._all_seen = time.monotonic()
            self._connected()

    def sync_requested(self) -> None:
        self._connected()

    def _connected(self) -> None:
        if "connect" in self.marks or "listening" not in self.marks:
            return
        self.marks["connect"] = time.monotonic()
        tracing.end(self._connect_sid, peers=self._peers, seen=self._seen)

    def block_synced(self, txs: int) -> None:
        if not self.done:
            self.blocks += 1
            self.txs += txs
            self._last_synced = time.monotonic()

    def era_finished(self, era: int, outcome: str) -> None:
        if self.done or "listening" not in self.marks:
            return
        if outcome == "synced":
            self.eras_synced += 1
        if outcome != "consensus":
            return
        self._connected()  # an era finished with no block to fetch
        now = time.monotonic()
        connected = self.marks["connect"]
        level = max(self._last_synced or connected, connected)
        self.marks["catch_up"], self.marks["rejoin"] = level, now
        self.rejoined_era = era
        tracing.completed(
            "node.recover.catch_up", connected, level, cat="recover",
            blocks=self.blocks, txs=self.txs,
        )
        tracing.completed(
            "node.recover.rejoin", level, now, cat="recover", era=era,
            eras_synced=self.eras_synced, all_seen_s=self._since_listening(self._all_seen),
        )
        if self.on_rejoined is not None:
            self.on_rejoined()

    def _since_listening(self, mark: Optional[float]) -> Optional[float]:
        return None if mark is None else round(mark - self.marks["listening"], 6)

    # -- what an operator (or the benchmark's child) reads -------------------
    def report(self) -> dict:
        """Seconds by phase, the marks themselves (time.monotonic(), one
        clock for every process of a host) and the counts."""
        out = {
            "height_at_open": self.height_at_open,
            "marks": dict(self.marks),
            "blocks": self.blocks,
            "txs": self.txs,
            "eras_synced": self.eras_synced,
            "rejoined_era": self.rejoined_era,
            "all_seen_s": self._since_listening(self._all_seen),
        }
        last = self.marks.get("listening")
        for p in PHASES:
            if p in self.marks:
                out[f"{p}_s"] = self.marks[p] - last
                last = self.marks[p]
        return out
