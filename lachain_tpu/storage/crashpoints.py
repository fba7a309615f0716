"""Deterministic crash-point injection for the storage/commit pipelines.

The seeded-fault machinery (network/faults.py) provokes loss on the WIRE;
this module provokes loss of the PROCESS at named points inside multi-write
commit pipelines — mid `write_batch`, between block persist and the
snapshot-index write, mid shrink stage, mid pool save — so crash-recovery
code (journal replay, fsck, resumable shrink) can be tested against every
torn state the pipelines can produce, reproducibly.

A :class:`CrashPlan` is a declarative schedule of :class:`CrashPoint`s:
each names an instrumented site and the 1-based traversal count at which it
fires. Firing is deterministic by construction — the Nth traversal of a
named site is the same event in every run of the same workload — which is
what makes a two-run repeat of a plan bit-identical.

Two harnesses execute a plan:

  * in-process (`mode="raise"`): the point raises :class:`InjectedCrash`
    (a BaseException, like SystemExit: ordinary ``except Exception``
    recovery paths cannot swallow it, because a real SIGKILL cannot be
    caught either);
  * real subprocess (`mode="sigkill"`): the point delivers SIGKILL to the
    current process, so the torn state on disk is produced by an actual
    process death, not a simulated one.

Instrumented sites call :func:`crash_point` — a no-op costing one global
read when no plan is armed. Subprocess harnesses arm via the
``LACHAIN_CRASH_POINTS`` environment variable (comma-separated
``NAME[@HIT][:MODE]`` specs), parsed by the CLI entrypoint at startup.

Instrumented point names:

  kv.write_batch.pre / .mid / .post   SqliteKV + LsmKV atomic batch
  block.persist.pre / .mid / .post    BlockManager._persist (mid = between
                                      the block batch and state.commit —
                                      the torn-block window fsck repairs)
  shrink.mark.height                  per-height mark checkpoint
  shrink.sweep.pre / shrink.clean.pre stage transitions
  pool.save.mid                       between pool admission and persist
  lsm.wal.encoded                     LsmKV only: the batch's WAL record
                                      partially written (torn tail), never
                                      fsynced/applied — replay discards it
  lsm.wal.fsynced                     LsmKV only: record durable but never
                                      acked/applied — replay applies it
  lsm.compact.mid                     LsmKV only: merged SST renamed into
                                      place, manifest swap lost — open()
                                      sweeps the orphan
  trie.merkle.subtree_streamed        streamed trie commit (StateManager):
                                      after an async subtrie node batch is
                                      enqueued on the WAL writer, before
                                      the root record — leaves durable
                                      orphan nodes with no referencing
                                      root; fsck-clean, replay recommits
  journal.barrier.pre                 consensus journal: records submitted
                                      to the WAL writer, the fsync not yet
                                      waited for, no frame carrying them
                                      sent — each is absent or re-armed

The lsm.* sites leave REAL torn native state (lsm.py calls the engine's
partial-execution debug APIs before dying), identical bytes on disk in
both harness modes.
"""
from __future__ import annotations

import os
import signal
import threading
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

ENV_VAR = "LACHAIN_CRASH_POINTS"

MODE_RAISE = "raise"
MODE_SIGKILL = "sigkill"


class InjectedCrash(BaseException):
    """In-process stand-in for a process death at a crash point."""

    def __init__(self, point: str, hit: int):
        super().__init__(f"injected crash at {point} (hit {hit})")
        self.point = point
        self.hit = hit


@dataclass(frozen=True)
class CrashPoint:
    """Fire at the `hit`-th traversal of the instrumented site `name`."""

    name: str
    hit: int = 1
    mode: str = MODE_RAISE


@dataclass(frozen=True)
class CrashPlan:
    """Deterministic crash schedule (faults.py FaultPlan idiom: a frozen
    declarative plan, live state lives in the session)."""

    points: Tuple[CrashPoint, ...] = ()

    def session(self) -> "CrashSession":
        return CrashSession(self)

    @staticmethod
    def parse_point(spec: str) -> CrashPoint:
        """"NAME[@HIT][:MODE]" — e.g. "block.persist.mid",
        "kv.write_batch.mid@3:sigkill"."""
        name, _, mode = spec.partition(":")
        mode = mode or MODE_RAISE
        if mode not in (MODE_RAISE, MODE_SIGKILL):
            raise ValueError(
                f"crash point {spec!r}: mode must be "
                f"{MODE_RAISE!r} or {MODE_SIGKILL!r}"
            )
        name, _, hit_s = name.partition("@")
        if not name:
            raise ValueError(f"crash point {spec!r}: empty name")
        return CrashPoint(name=name, hit=int(hit_s) if hit_s else 1, mode=mode)

    @classmethod
    def parse(cls, specs) -> "CrashPlan":
        return cls(points=tuple(cls.parse_point(s) for s in specs if s))

    def encode_env(self) -> str:
        """The ENV_VAR value that re-arms this plan in a subprocess."""
        return ",".join(
            f"{p.name}@{p.hit}:{p.mode}" for p in self.points
        )


class CrashSession:
    """One armed execution of a CrashPlan: traversal counters + fire log."""

    def __init__(self, plan: CrashPlan):
        self.plan = plan
        self._lock = threading.Lock()
        self.hits: Dict[str, int] = {}
        self.fired: List[Tuple[str, int]] = []
        self._by_name: Dict[str, List[CrashPoint]] = {}
        for p in plan.points:
            self._by_name.setdefault(p.name, []).append(p)

    def visit(self, name: str) -> Optional[CrashPoint]:
        """Count one traversal of `name`; return the point due to fire."""
        with self._lock:
            count = self.hits.get(name, 0) + 1
            self.hits[name] = count
        for p in self._by_name.get(name, ()):
            if p.hit == count:
                self.fired.append((name, count))
                return p
        return None

    @property
    def stats(self) -> Dict[str, object]:
        return {"visited": dict(self.hits), "fired": list(self.fired)}


# -- global arming (one plan per process, like a fault filter per hub) -------

_session: Optional[CrashSession] = None


def arm(plan: CrashPlan) -> CrashSession:
    global _session
    _session = plan.session()
    return _session


def disarm() -> Optional[CrashSession]:
    global _session
    s, _session = _session, None
    return s


def active() -> Optional[CrashSession]:
    return _session


@contextmanager
def armed(plan: CrashPlan):
    s = arm(plan)
    try:
        yield s
    finally:
        disarm()


def arm_from_env() -> Optional[CrashSession]:
    """Arm from LACHAIN_CRASH_POINTS (the subprocess harness path); no-op
    when unset. Called by the CLI entrypoint so a child `lachain-tpu run`
    executes the parent's plan."""
    spec = os.environ.get(ENV_VAR, "")
    if not spec:
        return None
    return arm(CrashPlan.parse(spec.split(",")))


def crash_point(name: str) -> None:
    """Instrumented-site hook. No-op unless a plan is armed and due."""
    s = _session
    if s is None:
        return
    point = s.visit(name)
    if point is None:
        return
    if point.mode == MODE_SIGKILL:
        os.kill(os.getpid(), signal.SIGKILL)
    raise InjectedCrash(name, point.hit)
