"""Era-lifecycle span recorder: where inside an era does the time go?

The metrics registry answers "how much / how often"; this module answers
"WHEN, nested under WHAT": era start -> sub-protocol lifetimes (RBC/BA/CC/
ACS/HB) -> TPKE flush -> block persist. Spans are recorded into a bounded
in-process ring buffer (zero dependencies, thread-safe) and exported as
Chrome `trace_event` JSON — load the output of `lachain-tpu trace` (RPC
`la_getTrace`) straight into chrome://tracing or Perfetto.

Protocol lifetimes are NOT stack-shaped (dozens overlap within one era), so
the primitive is a begin()/end() handle pair rather than only a context
manager; `span()` wraps the common scoped case. The 60 s stall watchdog
attaches `open_stack_str()` to its report so a stall names the exact
protocol (and flush/persist phase) it is stuck inside.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager, nullcontext
from typing import Any, Callable, Dict, List, Optional

# re-entrant: a collector run while the lock is held may finalise a native
# source's owner, whose close() drains into this module on the same thread
_lock = threading.RLock()
_ids = itertools.count(1)
# finished spans, oldest evicted first; 8192 spans ≈ a few dozen eras at
# N=16 — enough history to explain a stall without unbounded growth.
# LACHAIN_TRACE_CAPACITY (env, or config observability.traceCapacity via
# set_capacity) sizes this ring and the native-engine rings, those that are
# registered and those built later (capacity()). 0 turns the recorder off:
# `_done.maxlen` is the one switch every entry point reads.
DEFAULT_CAPACITY = max(int(os.environ.get("LACHAIN_TRACE_CAPACITY") or 8192), 0)
_done: deque = deque(maxlen=DEFAULT_CAPACITY)
_OFF = nullcontext(0)  # what span() and wait() hand out while off
_open: "Dict[int, _Span]" = {}
# monotonic epoch so exported timestamps are small positive microseconds
_epoch = time.monotonic()

# -- native flight-recorder merge state --------------------------------------
# Sources (the native consensus engine, each native LSM store) register a
# drain callback returning ready-made event dicts: {name, cat, start, end,
# args, pid, tid, tname}. `start`/`end` are time.monotonic() seconds (the
# source applies its clock-offset handshake before handing events over).
# name -> (drain, resize or None)
_native_sources: "Dict[str, tuple]" = {}
_native_done: deque = deque(maxlen=DEFAULT_CAPACITY)
# ring evictions (silent truncation made visible: satellite of ISSUE 6)
_py_dropped = 0


def _count_drop(n: int = 1) -> None:
    """Caller holds _lock. Mirrors the drop into the metrics registry."""
    global _py_dropped
    _py_dropped += n
    try:
        from . import metrics

        metrics.inc(
            "trace_events_dropped_total", n, labels={"source": "python"}
        )
    except Exception:  # metrics must never break the recorder
        pass


def dropped_total() -> int:
    """Python-ring evictions since start (native rings report their own)."""
    with _lock:
        return _py_dropped


class _Span:
    __slots__ = ("sid", "name", "cat", "start", "end", "args")

    def __init__(self, sid: int, name: str, cat: str, start: float, args):
        self.sid = sid
        self.name = name
        self.cat = cat
        self.start = start
        self.end: Optional[float] = None
        self.args: Dict[str, Any] = args

    def to_dict(self, now: Optional[float] = None) -> dict:
        end = self.end if self.end is not None else now
        return {
            "id": self.sid,
            "name": self.name,
            "cat": self.cat,
            "start": self.start,
            "end": end,
            "open": self.end is None,
            "args": dict(self.args),
        }


def begin(name: str, cat: str = "era", **args) -> int:
    """Open a span; returns its id (pass to end()/annotate())."""
    if not _done.maxlen:
        return 0
    sid = next(_ids)
    sp = _Span(sid, name, cat, time.monotonic(), args)
    with _lock:
        _open[sid] = sp
    return sp.sid


def annotate(sid: int, **args) -> None:
    """Merge args into a still-open span (no-op once closed)."""
    if not _done.maxlen:
        return
    with _lock:
        sp = _open.get(sid)
        if sp is not None:
            sp.args.update(args)


def end(sid: int, **args) -> None:
    """Close a span; idempotent (a GC sweep and a normal completion may
    both try to close the same protocol span)."""
    if not _done.maxlen:
        return
    with _lock:
        sp = _open.pop(sid, None)
        if sp is None:
            return
        sp.end = time.monotonic()
        if args:
            sp.args.update(args)
        if _done.maxlen is not None and len(_done) == _done.maxlen:
            _count_drop()
        _done.append(sp)


def instant(name: str, cat: str = "era", **args) -> None:
    """Record a zero-duration event (block persisted, watchdog firing)."""
    if not _done.maxlen:
        return
    sp = _Span(next(_ids), name, cat, time.monotonic(), args)
    sp.end = sp.start
    with _lock:
        if _done.maxlen is not None and len(_done) == _done.maxlen:
            _count_drop()
        _done.append(sp)


def completed(name: str, start: float, end: float, cat: str = "era", **args) -> None:
    """Record a span whose ends are known only in hindsight (a restart's
    catch-up ends at the last block it synced, which it knows once it has
    rejoined): `start` and `end` on time.monotonic()."""
    if not _done.maxlen:
        return
    sp = _Span(next(_ids), name, cat, start, args)
    sp.end = max(end, start)
    with _lock:
        if _done.maxlen is not None and len(_done) == _done.maxlen:
            _count_drop()
        _done.append(sp)


def span(name: str, cat: str = "era", **args):
    """Scoped begin/end; yields the span id for annotate()."""
    return _span(name, cat, args) if _done.maxlen else _OFF


@contextmanager
def _span(name: str, cat: str, args: dict):
    sid = begin(name, cat, **args)
    try:
        yield sid
    finally:
        end(sid)


# The named wait buckets era_report() decomposes idle into. Every blocking
# point in an era thread tags itself with the resource it waits on; the
# remainder (time nothing claims) is reported as idle_unattributed.
WAIT_RESOURCES = ("net", "crypto_flush", "device", "fsync", "sched")
# Overlap precedence between wait intervals: specific resources outrank
# the broad ones. `net` is the catch-all (the hub read loop waits for
# nearly all wall time) so it only owns segments nothing else claims;
# `sched` (the native dispatch loop's queue-empty gap) brackets whatever
# host-side work starved it, so the specific cause wins when present.
_WAIT_PRIORITY = {
    "device": 0,
    "fsync": 1,
    "crypto_flush": 2,
    "sched": 3,
    "net": 4,
}


def wait(resource: str, **args):
    """Scoped wait-state span: wraps a blocking call (queue get, fsync,
    device sync, socket read) so era_report() can attribute the idle it
    causes to `resource`. Also feeds the wait_seconds{resource} histogram."""
    return _wait(resource, args) if _done.maxlen else _OFF


@contextmanager
def _wait(resource: str, args: dict):
    sid = begin(f"wait.{resource}", cat="wait", resource=resource, **args)
    t0 = time.monotonic()
    try:
        yield sid
    finally:
        end(sid)
        try:
            from . import metrics

            metrics.observe_hist(
                "wait_seconds",
                time.monotonic() - t0,
                labels={"resource": resource},
            )
        except Exception:  # metrics must never break the waiter
            pass


# -- the loop thread's time, exclusive by part --------------------------------
#
# Spans answer WHEN; for what runs a thousand times an era (a frame, a
# consensus message, an admission) a span each costs more than it is worth.
# The ledger answers HOW MUCH, by part, while an era runs on the thread
# (ledger_begin .. ledger_end): account(part) charges the calling thread's
# wall time to `part` and pauses the part open beneath it, the select()
# wrapper of loop_idle() charges a parked loop to `idle`, and busy time no
# scope claimed goes to `other`. So idle + other + the named parts IS the
# thread's wall time over its eras, by construction. The sums reach the
# metrics registry by difference (_fold), as LOOP_METRIC{part} and, for the
# parts that are a consensus family, DISPATCH_METRIC{family}: the counter the
# native engine writes (consensus/native_rt.py _fold_dispatch).
LOOP_METRIC = "node_loop_seconds_total"
DISPATCH_METRIC = "consensus_engine_dispatch_seconds_total"
SCOPES_METRIC = "node_loop_scopes_total"
DISPATCH_FAMILIES = ("rbc", "ba", "coin", "tpke", "commit")
_FOLD_EVERY = 0.1  # seconds between folds at a park; an era's end always folds


class _Ledger:
    """One thread's sums. `mark` is when the part on top of `stack` was
    last charged: every boundary charges `now - mark` to the top and moves
    the mark, so no second is charged twice or to nobody. `sums` is `held`
    while an era runs on the thread and `spilt` otherwise: a scope costs the
    same either way, and what it times outside every era is nobody's."""

    __slots__ = (
        "sums", "held", "spilt", "stack", "mark", "eras", "scopes", "folded",
        "folded_scopes", "folded_at",
    )

    def __init__(self):
        self.held: Dict[str, float] = defaultdict(float)
        self.spilt: Dict[str, float] = defaultdict(float)
        self.sums = self.spilt
        self.stack = ["other"]  # the base: busy time no scope claims
        self.mark = time.monotonic()
        self.eras = 0  # ledger_begin() calls not yet ended
        self.scopes = 0
        self.folded: Dict[str, float] = {}
        self.folded_scopes = 0
        self.folded_at = self.mark


class _Ledgers(threading.local):
    # a thread's first touch builds its own ledger: a scope on an RPC
    # executor or the signer's reader never enters the loop thread's
    def __init__(self):
        self.own = _Ledger()


_ledgers = _Ledgers()


class _Account:
    """The scope account() hands out; one shared object a part, the state
    lives in the calling thread's ledger."""

    __slots__ = ("part",)

    def __init__(self, part: str):
        self.part = part

    def __enter__(self):
        led = _ledgers.own
        now = time.monotonic()
        stack = led.stack
        led.sums[stack[-1]] += now - led.mark
        led.mark = now
        stack.append(self.part)

    def __exit__(self, *exc):
        led = _ledgers.own
        now = time.monotonic()
        led.stack.pop()
        led.sums[self.part] += now - led.mark
        led.mark = now
        led.scopes += 1


_accounts: Dict[str, _Account] = {}


def account(part: str):
    """Scope that charges the calling thread's wall time to `part`, less
    whatever nested scopes claim: a nested scope's seconds are never also
    its parent's. Two clock reads, a push and a pop; no lock, no span, no
    metrics call. Wrap SYNCHRONOUS stretches only: a scope held across an
    `await` would bill every other callback the loop runs meanwhile to its
    part (tests/test_loop_ledger.py reads the source for one). With the
    recorder off (capacity 0) it is the null context and nothing moves."""
    if not _done.maxlen:
        return _OFF
    scope = _accounts.get(part)
    if scope is None:
        scope = _accounts[part] = _Account(part)
    return scope


def _cut(led: _Ledger) -> float:
    now = time.monotonic()
    led.sums[led.stack[-1]] += now - led.mark
    led.mark = now
    return now


def _fold(led: _Ledger, now: float) -> None:
    """Add what the ledger gained since the last fold to the registry."""
    from . import metrics

    led.folded_at = now
    folded = led.folded
    for part, secs in led.held.items():
        moved = secs - folded.get(part, 0.0)
        if moved <= 0.0:
            continue
        folded[part] = secs
        if part in DISPATCH_FAMILIES:
            metrics.inc(DISPATCH_METRIC, moved, labels={"family": part})
        else:
            metrics.inc(LOOP_METRIC, moved, labels={"part": part})
    if led.scopes > led.folded_scopes:
        metrics.inc(SCOPES_METRIC, led.scopes - led.folded_scopes)
        led.folded_scopes = led.scopes


def ledger_begin() -> Optional[Dict[str, float]]:
    """An era starts on the calling thread: from here to ledger_end() the
    thread's time is held, by part. Returns the ledger as it stands, for
    ledger_end(); None while the recorder is off. Eras of a fleet on one
    loop overlap: the ledger holds until the last one ends."""
    if not _done.maxlen:
        return None
    led = _ledgers.own
    _cut(led)
    led.eras += 1
    led.sums = led.held
    return dict(led.held)


def ledger_end(began: Optional[Dict[str, float]]) -> Dict[str, Dict[str, float]]:
    """The era ended: what the thread's ledger gained since ledger_begin(),
    as arguments for the era's span — `loop_s` by part and `dispatch_s` by
    consensus family, which together sum to the time between the two calls
    — and everything folded into the registry."""
    if began is None:
        return {}
    led = _ledgers.own
    now = _cut(led)
    led.eras = max(led.eras - 1, 0)
    if not led.eras:
        led.sums = led.spilt
        led.spilt.clear()
    _fold(led, now)
    loop_s: Dict[str, float] = {}
    dispatch_s: Dict[str, float] = {}
    for part, secs in led.held.items():
        moved = secs - began.get(part, 0.0)
        if moved > 0.0:
            into = dispatch_s if part in DISPATCH_FAMILIES else loop_s
            into[part] = round(moved, 6)
    return {"loop_s": loop_s, "dispatch_s": dispatch_s}


# selector -> how many loop_idle() scopes are open on its loop
_idle_scopes: Dict[Any, int] = {}


@contextmanager
def loop_idle(name: str, cat: str = "wait", **args):
    """While the scope is open, every blocking select() of the running
    asyncio loop is one span `name`: the stretches in which the loop's
    thread has nothing ready — no frame read, no handler, no timer due.
    One thread parks in one select at a time, so the spans never overlap
    and their lengths add (what a span a connection's read cannot give).
    The same stretches are the ledger's part `idle` while an era runs on
    the thread (ledger_begin), and each park is where the ledger folds.
    Scopes nest (a fleet of nodes on one loop): the outermost one's name
    and args are recorded. Loops without a selector (not asyncio's own)
    record nothing."""
    import asyncio

    selector = getattr(asyncio.get_running_loop(), "_selector", None)
    if selector is None or not _done.maxlen:
        yield
        return
    if selector not in _idle_scopes:
        inner = selector.select
        led = _ledgers.own  # the loop's thread installs and runs the wrapper

        def select(timeout=None):
            if timeout is not None and timeout <= 0:
                return inner(timeout)  # a poll: work is ready
            sid = begin(name, cat, **args)
            park = _cut(led)
            if park - led.folded_at > _FOLD_EVERY:
                _fold(led, park)
            try:
                return inner(timeout)
            finally:
                led.mark = wake = time.monotonic()
                led.sums["idle"] += wake - park
                end(sid)

        selector.select = select  # shadows the method on this instance
    _idle_scopes[selector] = _idle_scopes.get(selector, 0) + 1
    try:
        yield
    finally:
        _idle_scopes[selector] -= 1
        if not _idle_scopes[selector]:
            del _idle_scopes[selector]
            del selector.select


def open_spans() -> List[dict]:
    """Snapshot of currently-open spans, oldest first (the watchdog's
    view of what the node is stuck inside)."""
    now = time.monotonic()
    with _lock:
        spans = sorted(_open.values(), key=lambda s: (s.start, s.sid))
        return [s.to_dict(now) for s in spans]


def open_stack_str() -> str:
    """Human one-liner of the open-span stack for stall reports:
    'era(era=7) > HoneyBadger > tpke.flush'."""
    parts = []
    for s in open_spans():
        era = s["args"].get("era")
        parts.append(
            f"{s['name']}(era={era})" if era is not None else s["name"]
        )
    return " > ".join(parts) if parts else "<no open spans>"


def snapshot(limit: Optional[int] = None) -> List[dict]:
    """Finished + open spans as plain dicts, oldest first."""
    now = time.monotonic()
    with _lock:
        done = list(_done)
        # the few open ones under the lock: annotate() may still write them
        out = [s.to_dict(now) for s in _open.values()]
    # a finished span no longer changes: its dict is built with the lock
    # free, so a long ring neither holds up the recording threads nor
    # allocates (and so collects) inside the lock
    out += [s.to_dict() for s in done]
    out.sort(key=lambda d: (d["start"], d["id"]))
    if limit is not None and limit > 0:
        out = out[-limit:]
    return out


def chrome_now_us() -> float:
    """'Now' on the exported Chrome ts axis (microseconds since this
    tracer's epoch). The anchor `la_time` serves so a fleet merger can
    align this node's trace axis with its own clock by RTT bracketing —
    the cross-node analogue of clock_offset()."""
    return (time.monotonic() - _epoch) * 1e6


# -- native flight-recorder merge --------------------------------------------


def clock_offset(native_now_ns: Callable[[], int], samples: int = 5) -> float:
    """Seconds to ADD to a native engine's monotonic ns/1e9 so its
    timestamps land on this tracer's time.monotonic axis. Both clocks are
    CLOCK_MONOTONIC on Linux, but the handshake keeps the alignment honest
    where the epochs differ: bracket the native read with two monotonic
    reads and keep the tightest bracket's midpoint."""
    best_width, best_off = None, 0.0
    for _ in range(max(samples, 1)):
        t0 = time.monotonic()
        ns = native_now_ns()
        t1 = time.monotonic()
        if best_width is None or (t1 - t0) < best_width:
            best_width = t1 - t0
            best_off = (t0 + t1) / 2 - ns / 1e9
    return best_off


def register_native_source(
    name: str,
    fn: Callable[[], List[dict]],
    resize: Optional[Callable[[int], None]] = None,
) -> None:
    """Register a drain callback for a native engine's trace ring.

    `fn` returns event dicts with monotonic-aligned `start`/`end` seconds
    (the binding applies its clock-offset handshake), plus `pid`, `tid`,
    `pname`, `tname` lane hints for the Chrome export. `resize(capacity)`
    is what set_capacity() calls to resize the engine's own ring (0 = off).
    Re-registering a name replaces the previous callbacks (engine restart)."""
    with _lock:
        _native_sources[name] = (fn, resize)


def unregister_native_source(name: str) -> None:
    with _lock:
        _native_sources.pop(name, None)


def drain_native() -> None:
    """Pull pending events out of every registered native ring into the
    merged buffer. Cheap when rings are empty; callers sprinkle this at
    quiescent points (era end, snapshot/export time)."""
    if not _done.maxlen:
        return
    with _lock:
        sources = list(_native_sources.values())
    for fn, _resize in sources:
        try:
            evs = fn()
        except Exception:
            # a closed engine must not poison the recorder; the owner
            # unregisters on close, this covers teardown races
            continue
        if not evs:
            continue
        with _lock:
            for ev in evs:
                if (
                    _native_done.maxlen is not None
                    and len(_native_done) == _native_done.maxlen
                ):
                    _count_drop()
                _native_done.append(ev)


def native_snapshot() -> List[dict]:
    """Drained native events as plain dicts, oldest first. Triggers a
    drain."""
    drain_native()
    with _lock:
        out = list(_native_done)
    out.sort(key=lambda d: (d.get("start", 0.0), d.get("tid", 0)))
    return [dict(d) for d in out]


PY_PID = 1  # Python host process lane group in the Chrome export
# the native consensus engine's lane group (LSM stores take 3 and up)
NATIVE_CONSENSUS_PID = 2


def _assign_lanes(spans: List[dict]) -> List[tuple]:
    """Per-category, nesting-preserving lane assignment.

    Within one category, each lane holds a stack of enclosing span end
    times: a span may join a lane only if the lane is idle at its start
    or the span nests fully inside the lane's innermost open span.
    Overlapping-but-not-nested spans (concurrent protocol instances)
    therefore land on separate rows, while parent/child pairs stay
    stacked on one row so Perfetto renders real nesting.

    Returns [(span_dict, category, lane_index)], input order preserved.
    """
    lanes_by_cat: Dict[str, List[List[float]]] = {}
    out = []
    for d in spans:
        cat = d["cat"] or "default"
        lanes = lanes_by_cat.setdefault(cat, [])
        placed = None
        for idx, stack in enumerate(lanes):
            while stack and stack[-1] <= d["start"]:
                stack.pop()
            if not stack or d["end"] <= stack[-1]:
                stack.append(d["end"])
                placed = idx
                break
        if placed is None:
            placed = len(lanes)
            lanes.append([d["end"]])
        out.append((d, cat, placed))
    return out


def to_chrome_trace(limit: Optional[int] = None) -> dict:
    """Chrome trace_event JSON (load in chrome://tracing / Perfetto).

    Python-host spans render under pid=1 with one labeled thread-row
    group per category (nesting preserved; concurrent instances fan out
    to numbered sibling rows). Drained native-engine events render under
    their own pids with the engine's real thread roles (WAL writer,
    flusher, compactor, per-validator dispatch) as named rows, and the
    consensus engine's callbacks (`cross.<op>` spans) on their validator's
    row there, so one export shows the whole cross-language timeline."""
    events: List[dict] = []
    # (pid, tid) -> row label; pid -> process label
    thread_names: Dict[tuple, str] = {}
    proc_names: Dict[int, str] = {PY_PID: "python-host"}

    tid_of: Dict[tuple, int] = {}

    def py_tid(cat: str, lane: int) -> int:
        key = (cat, lane)
        if key not in tid_of:
            tid_of[key] = len(tid_of) + 1
            label = cat if lane == 0 else f"{cat}#{lane}"
            thread_names[(PY_PID, tid_of[key])] = label
        return tid_of[key]

    for d, cat, lane in _assign_lanes(snapshot(limit)):
        args = dict(d["args"])
        if d["open"]:
            args["open"] = True
        if d["name"].startswith("cross.") and "vid" in args:
            # an engine->Python callback renders on its validator's row of
            # the native engine's process, beside that engine's ring records
            pid, tid = NATIVE_CONSENSUS_PID, int(args["vid"])
            proc_names.setdefault(pid, "native-consensus")
            thread_names[(pid, tid)] = f"validator-{tid}"
        else:
            pid, tid = PY_PID, py_tid(cat, lane)
        events.append(
            {
                "name": d["name"],
                "cat": d["cat"],
                "ph": "X",
                "pid": pid,
                "tid": tid,
                "ts": round((d["start"] - _epoch) * 1e6, 1),
                "dur": round(max((d["end"] - d["start"]) * 1e6, 0.0), 1),
                "args": args,
            }
        )

    for ev in native_snapshot():
        pid = int(ev.get("pid", NATIVE_CONSENSUS_PID))
        tid = int(ev.get("tid", 0))
        if ev.get("pname"):
            proc_names[pid] = ev["pname"]
        if ev.get("tname"):
            thread_names[(pid, tid)] = ev["tname"]
        events.append(
            {
                "name": ev["name"],
                "cat": ev.get("cat", "native"),
                "ph": "X",
                "pid": pid,
                "tid": tid,
                "ts": round((ev["start"] - _epoch) * 1e6, 1),
                "dur": round(
                    max((ev["end"] - ev["start"]) * 1e6, 0.0), 1
                ),
                "args": dict(ev.get("args") or {}),
            }
        )

    meta: List[dict] = []
    for pid, label in sorted(proc_names.items()):
        meta.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": label},
            }
        )
    for (pid, tid), label in sorted(thread_names.items()):
        meta.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": label},
            }
        )
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def summary() -> dict:
    """Per-span-name aggregate: {name: {count, total_ms, max_ms, open}}."""
    agg: Dict[str, dict] = {}
    for d in snapshot():
        ent = agg.setdefault(
            d["name"], {"count": 0, "total_ms": 0.0, "max_ms": 0.0, "open": 0}
        )
        ms = (d["end"] - d["start"]) * 1e3
        ent["count"] += 1
        ent["total_ms"] = round(ent["total_ms"] + ms, 3)
        ent["max_ms"] = round(max(ent["max_ms"], ms), 3)
        if d["open"]:
            ent["open"] += 1
    return agg


# -- era phase attribution ---------------------------------------------------

# Report columns, and the precedence used when intervals overlap: a span
# counted as TPKE decrypt (device call) wins over the protocol span it is
# nested inside. Idle is derived (wall − attributed), so the table always
# sums to era wall time up to clamp error.
PHASES = (
    "propose",
    "rbc",
    "rbc_device",
    "ba",
    "coin",
    "tpke_verify",
    "tpke_decrypt",
    "exec",
    "merkle",
    "commit",
)
_PHASE_PRIORITY = {
    "tpke_decrypt": 0,
    "tpke_verify": 1,
    # merkle outranks exec: the merkle.freeze span nests inside exec.block,
    # and commit attribution must separate hashing from tx execution.
    # exec outranks commit: the block-execution span nests inside the
    # root_produce commit crossing
    "merkle": 2,
    "exec": 3,
    "propose": 4,
    "commit": 5,
    "coin": 6,
    "ba": 7,
    "rbc": 8,
    # rs.device spans nest inside the rbc.flush span: the device column must
    # win that overlap so host-vs-device RS time splits cleanly
    "rbc_device": 1.5,
}

# Python span name -> phase. Parent/orchestrator spans (era, HoneyBadger,
# CommonSubset, RootProtocol) are deliberately absent: their time is the
# sum of their children plus idle, so attributing them would double count.
# The protocol names are LIFETIME spans: for the Python engine the rbc / ba /
# coin columns they fill are coverage (a lifetime is mostly waiting for
# peers), and the work is the era's `dispatch_s` — exclusive seconds by
# family, the meaning these columns have under the native engine.
_SPAN_PHASE = {
    "consensus.propose": "propose",
    "ReliableBroadcast": "rbc",
    "rbc.flush": "rbc",
    "rs.device": "rbc_device",
    "BinaryAgreement": "ba",
    "BinaryBroadcast": "ba",
    "CommonCoin": "coin",
    "hb.era_decrypt": "tpke_decrypt",
    "hb.apply_era_results": "tpke_decrypt",
    "exec.block": "exec",
    "merkle.freeze": "merkle",
}

# Engine->Python callback -> phase, keyed by the op of its span `cross.<op>`
# (consensus/native_rt.py _cross_begin; ops: native_hosts.py XO_NAMES). The
# span is the one timing of a callback.
_CROSS_PHASE = {
    "coin_sign": "coin",
    "coin_combine": "coin",
    "coin_result": "coin",
    "hb_acs": "tpke_verify",
    "hb_queue": "tpke_decrypt",
    "hb_done": "tpke_decrypt",
    "rbc_encode": "rbc",
    "rbc_need": "rbc",
    "root_input": "propose",
    "root_sign": "commit",
    "root_verify": "commit",
    "root_produce": "commit",
}

# Family of consensus_engine_dispatch_seconds_total -> phase: the engine's
# exclusive message-dispatch seconds, which reach the report as the
# `dispatch_s` argument of the `engine.pump` span that folded them into the
# counter (native_rt.py _run_engine).
_DISPATCH_PHASE = {
    "rbc": "rbc",
    "ba": "ba",
    "coin": "coin",
    "tpke": "tpke_decrypt",
    "commit": "commit",
}


def _sweep(intervals: List[tuple], lo: float, hi: float) -> Dict[str, float]:
    """Exclusive per-phase time from possibly-overlapping phase intervals,
    clipped to [lo, hi]; where intervals overlap the highest-priority
    phase owns the time (so nested spans never double count)."""
    edges = {lo, hi}
    clipped = []
    for phase, s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            clipped.append((phase, s, e))
            edges.add(s)
            edges.add(e)
    cuts = sorted(edges)
    out = {p: 0.0 for p in PHASES}
    for i in range(len(cuts) - 1):
        s, e = cuts[i], cuts[i + 1]
        best = None
        for phase, ps, pe in clipped:
            if ps <= s and pe >= e:
                if best is None or (
                    _PHASE_PRIORITY[phase] < _PHASE_PRIORITY[best]
                ):
                    best = phase
        if best is not None:
            out[best] += e - s
    return out


def _sweep_waits(
    phase_iv: List[tuple],
    wait_iv: List[tuple],
    lo: float,
    hi: float,
) -> Dict[str, float]:
    """Exclusive per-resource wait time on the stretches of [lo, hi] that
    NO phase interval covers: any attributed phase time outranks every
    wait (a wait span bracketing real work must not double count), and
    overlapping waits resolve by _WAIT_PRIORITY."""
    edges = {lo, hi}
    phases = []
    for _, s, e in phase_iv:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            phases.append((s, e))
            edges.add(s)
            edges.add(e)
    waits = []
    for res, s, e in wait_iv:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            waits.append((res, s, e))
            edges.add(s)
            edges.add(e)
    cuts = sorted(edges)
    out = {r: 0.0 for r in WAIT_RESOURCES}
    for i in range(len(cuts) - 1):
        s, e = cuts[i], cuts[i + 1]
        if any(ps <= s and pe >= e for ps, pe in phases):
            continue
        best = None
        for res, ws, we in waits:
            if ws <= s and we >= e:
                pr = _WAIT_PRIORITY.get(res, len(_WAIT_PRIORITY))
                if best is None or pr < best[0]:
                    best = (pr, res)
        if best is not None:
            out.setdefault(best[1], 0.0)
            out[best[1]] += e - s
    return out


def _critical_path(intervals: List[tuple], lo: float, hi: float) -> dict:
    """Longest blocking chain through one era window.

    `intervals` are (kind, name, start, end) with kind in
    {"phase", "wait"}. Walk BACKWARDS from the era end (the commit): at
    each cursor pick the covering interval that reaches furthest back and
    emit one segment per hop; stretches nothing covers become
    "gap"/"unattributed" segments (the engine's dispatch seconds have no
    intervals, so engine dispatch time lands here, bounded by callbacks
    and wait records on either side). By construction the segments tile
    [lo, hi], so their lengths sum to the era wall."""
    eps = 1e-9
    iv = [
        (kind, name, max(s, lo), min(e, hi))
        for kind, name, s, e in intervals
        if min(e, hi) > max(s, lo)
    ]
    segs: List[dict] = []
    cursor = hi
    while cursor - lo > eps:
        best = None
        for kind, name, s, e in iv:
            if s < cursor - eps and e >= cursor - eps:
                if best is None or s < best[2]:
                    best = (kind, name, s)
        if best is not None:
            start = max(best[2], lo)
            segs.append(
                {"kind": best[0], "name": best[1],
                 "start": start, "end": cursor}
            )
            cursor = start
        else:
            prev = lo
            for _, _, s, e in iv:
                if e < cursor - eps and e > prev:
                    prev = e
            segs.append(
                {"kind": "gap", "name": "unattributed",
                 "start": prev, "end": cursor}
            )
            cursor = prev
    segs.reverse()
    merged: List[dict] = []
    for sg in segs:
        if (
            merged
            and merged[-1]["kind"] == sg["kind"]
            and merged[-1]["name"] == sg["name"]
        ):
            merged[-1]["end"] = sg["end"]
        else:
            merged.append(dict(sg))
    out_segs = [
        {
            "kind": sg["kind"],
            "name": sg["name"],
            "start_s": round(sg["start"] - lo, 6),
            "end_s": round(sg["end"] - lo, 6),
            "dur_s": round(sg["end"] - sg["start"], 6),
        }
        for sg in merged
    ]
    top = sorted(out_segs, key=lambda s: -s["dur_s"])[:5]
    return {
        "total_s": round(sum(s["dur_s"] for s in out_segs), 6),
        "segments": out_segs,
        "top": [
            {"kind": s["kind"], "name": s["name"], "dur_s": s["dur_s"]}
            for s in top
        ],
    }


def era_report(
    spans: Optional[List[dict]] = None,
    native: Optional[List[dict]] = None,
) -> dict:
    """Per-era phase attribution: where does era wall time go?

    Combines three sources, all of them spans: Python protocol/crypto
    spans (interval sweep with nesting priority), the native engine's
    callbacks (`cross.<op>`, swept with them), and the engine's exclusive
    dispatch seconds by family (the `dispatch_s` of each `engine.pump`:
    what that call added to consensus_engine_dispatch_seconds_total).
    A served node's `era` span carries the loop thread's ledger over the
    era (Node.run_era, ledger_end): it is passed through as `loop_s` by
    part and `dispatch_s` by family, which together sum to the span. They
    ADD to the report: under the Python engine the rbc / ba / coin phase
    columns are swept from protocol lifetimes and so are coverage, the
    work is in `dispatch_s`.
    Idle = wall − attributed, clamped at 0, then
    DECOMPOSED into named wait buckets (waits_s, from wait.* spans and
    native wait records) plus an idle_unattributed remainder — the
    invariant is buckets + remainder == the old idle value. Each era also
    carries a critical_path block: the longest blocking chain walked
    backwards from the era's end, whose segments tile the era wall. The
    direct input for deciding what to overlap when pipelining eras
    (ROADMAP item 1)."""
    if spans is None:
        spans = snapshot()
    if native is None:
        native = native_snapshot()

    # era window = union over every node's "era" span for that era number
    windows: Dict[int, List[float]] = {}
    # era -> (length, args) of its longest `era` span that carries a ledger:
    # nodes of a fleet on one loop share the thread, so each of their spans
    # holds the thread's split over its own stretch and they must not add
    ledgers: Dict[int, tuple] = {}
    for d in spans:
        if d["name"] == "era" and d["args"].get("era") is not None:
            era = int(d["args"]["era"])
            w = windows.setdefault(era, [d["start"], d["end"]])
            w[0] = min(w[0], d["start"])
            w[1] = max(w[1], d["end"])
            length = d["end"] - d["start"]
            if "loop_s" in d["args"] and length > ledgers.get(era, (-1.0,))[0]:
                ledgers[era] = (length, d["args"])

    per_era_iv: Dict[int, List[tuple]] = {e: [] for e in windows}
    dispatch: Dict[int, Dict[str, float]] = {}
    for d in spans:
        era = d["args"].get("era")
        if era is None or int(era) not in per_era_iv:
            continue
        era = int(era)
        name = d["name"]
        if name == "engine.pump":
            acc = dispatch.setdefault(era, {})
            for family, secs in (d["args"].get("dispatch_s") or {}).items():
                phase = _DISPATCH_PHASE.get(family)
                if phase is not None:
                    acc[phase] = acc.get(phase, 0.0) + float(secs)
            continue
        if name.startswith("cross."):
            phase = _CROSS_PHASE.get(name[len("cross."):])
        else:
            phase = _SPAN_PHASE.get(name)
        if phase is not None:
            per_era_iv[era].append((phase, d["start"], d["end"]))

    # mesh device-busy windows (parallel/mesh.MeshEraPipeline spans the
    # kernel dispatch -> result-ready interval as "mesh.device"): these are
    # era-agnostic — the pipeline serves every validator's chunks — so they
    # attribute to eras by time overlap with each era window
    mesh_spans = [
        d for d in spans
        if d["name"] == "mesh.device" and d["end"] is not None
    ]

    # wait-state intervals (Python wait.* spans + native wait records):
    # attributed to eras by time overlap — a hub read wait or an LSM
    # fsync wait serves the node, not one era, so clipping is the honest
    # split (same rule as mesh.device above)
    wait_iv_all: List[tuple] = []
    for d in spans:
        if d["end"] is None:
            continue
        if d["cat"] == "wait":
            res = d["args"].get("resource") or "net"
            wait_iv_all.append((res, d["start"], d["end"]))
        elif d["name"] == "era.net_idle":
            # the loop parked in select() while the era waits (loop_idle):
            # the node's one measure of waiting for the network
            wait_iv_all.append(("net", d["start"], d["end"]))

    for ev in native:
        if ev.get("cat") == "native.wait":
            res = (ev.get("args") or {}).get("resource") or "sched"
            wait_iv_all.append((res, ev["start"], ev["end"]))

    eras = []
    for era in sorted(windows):
        lo, hi = windows[era]
        wall = max(hi - lo, 0.0)
        # pipelining overlap: how much of this era's window was shared
        # with ANY other in-flight era (intersection with the union of the
        # other windows). 0 everywhere means the eras ran sequentially.
        other = sorted(
            (max(s, lo), min(e, hi))
            for o, (s, e) in windows.items()
            if o != era and min(e, hi) > max(s, lo)
        )
        overlap = 0.0
        cur_s = cur_e = None
        for s, e in other:
            if cur_e is None or s > cur_e:
                if cur_e is not None:
                    overlap += cur_e - cur_s
                cur_s, cur_e = s, e
            else:
                cur_e = max(cur_e, e)
        if cur_e is not None:
            overlap += cur_e - cur_s
        phases = _sweep(per_era_iv[era], lo, hi)
        # engine dispatch time is measured OUTSIDE the callbacks (their
        # time subtracted natively), so it is exclusive of every interval
        # above and adds linearly
        for phase, secs in dispatch.get(era, {}).items():
            phases[phase] += secs
        attributed = sum(phases.values())
        idle = max(wall - attributed, 0.0)
        # idle decomposition: exclusive wait coverage on the un-attributed
        # stretches of the window. The dispatch seconds above occupy
        # unswept wall time, so raw wait coverage can exceed the idle
        # residual; scale the buckets down proportionally so
        # buckets + remainder always equal the old idle value exactly.
        wait_iv = [
            (res, s, e) for res, s, e in wait_iv_all
            if min(e, hi) > max(s, lo)
        ]
        waits = _sweep_waits(per_era_iv[era], wait_iv, lo, hi)
        wsum = sum(waits.values())
        if wsum > idle and wsum > 0:
            scale = idle / wsum
            waits = {r: v * scale for r, v in waits.items()}
            wsum = idle
        unattr = max(idle - wsum, 0.0)
        cpath = _critical_path(
            [("phase", p, s, e) for p, s, e in per_era_iv[era]]
            + [("wait", res, s, e) for res, s, e in wait_iv],
            lo,
            hi,
        )
        # per-device utilization row: union of mesh.device (dispatch ->
        # ready) windows clipped to this era, all_gather bytes pro-rated by
        # the clipped fraction. busy/wall is an upper bound on device
        # utilization (the ready edge is observed when the caller blocks)
        dev_iv = []
        dev_mb = 0.0
        dev_n = 0
        for d in mesh_spans:
            cs, ce = max(d["start"], lo), min(d["end"], hi)
            if ce <= cs:
                continue
            dev_iv.append((cs, ce))
            dur = d["end"] - d["start"]
            if dur > 0:
                dev_mb += float(
                    d["args"].get("allgather_mb", 0.0)
                ) * (ce - cs) / dur
            dev_n = max(dev_n, int(d["args"].get("devices", 0)))
        dev_iv.sort()
        busy = 0.0
        cur_s = cur_e = None
        for cs, ce in dev_iv:
            if cur_e is None or cs > cur_e:
                if cur_e is not None:
                    busy += cur_e - cur_s
                cur_s, cur_e = cs, ce
            else:
                cur_e = max(cur_e, ce)
        if cur_e is not None:
            busy += cur_e - cur_s
        eras.append(
            {
                "era": era,
                "wall_s": round(wall, 6),
                "phases_s": {p: round(phases[p], 6) for p in PHASES},
                "idle_s": round(idle, 6),
                "waits_s": {
                    r: round(waits.get(r, 0.0), 6) for r in WAIT_RESOURCES
                },
                "idle_unattributed_s": round(unattr, 6),
                "idle_unattributed_fraction": round(unattr / idle, 4)
                if idle > 0
                else 0.0,
                "critical_path": cpath,
                "overlap_s": round(overlap, 6),
                "attributed_s": round(attributed, 6),
                "coverage": round(
                    (attributed + idle) / wall, 4
                ) if wall > 0 else 1.0,
                "device": {
                    "busy_s": round(busy, 6),
                    "util": round(busy / wall, 4) if wall > 0 else 0.0,
                    "allgather_mb": round(dev_mb, 3),
                    "mesh_devices": dev_n,
                },
            }
        )
        if era in ledgers:
            for key in ("loop_s", "dispatch_s"):
                eras[-1][key] = dict(ledgers[era][1].get(key) or {})
    # Byzantine pressure per era (evidence.py per-process registry): how
    # many NEW equivocation / invalid-share records this process minted
    # while the era ran — `trace --era-report` surfaces attack visibility
    # next to the phase timings it distorts
    try:
        from ..consensus.evidence import era_counts

        by_era = era_counts()
        for ent in eras:
            ent["byzantine"] = dict(
                by_era.get(ent["era"], {"equivocation": 0, "invalid_share": 0})
            )
    except Exception:
        pass  # evidence module must never break the report
    return {"eras": eras, "phases": list(PHASES)}


def era_report_table(report: Optional[dict] = None) -> str:
    """Plain-text per-era phase table (CLI `trace --era-report`)."""
    if report is None:
        report = era_report()
    cols = (
        ["era", "wall_s"] + list(PHASES)
        + ["idle_s"] + [f"w:{r}" for r in WAIT_RESOURCES]
        + ["unattr_s", "overlap_s", "dev_util", "equiv", "badshare"]
    )
    rows = [cols]
    for ent in report["eras"]:
        dev = ent.get("device") or {}
        waits = ent.get("waits_s") or {}
        byz = ent.get("byzantine") or {}
        rows.append(
            [str(ent["era"]), f"{ent['wall_s']:.3f}"]
            + [f"{ent['phases_s'][p]:.3f}" for p in PHASES]
            + [f"{ent['idle_s']:.3f}"]
            + [f"{waits.get(r, 0.0):.3f}" for r in WAIT_RESOURCES]
            + [
                f"{ent.get('idle_unattributed_s', 0.0):.3f}",
                f"{ent.get('overlap_s', 0.0):.3f}",
                f"{dev.get('util', 0.0):.3f}",
                str(byz.get("equivocation", 0)),
                str(byz.get("invalid_share", 0)),
            ]
        )
    if len(rows) == 1:
        return "<no completed eras in trace ring>"
    widths = [max(len(r[i]) for r in rows) for i in range(len(cols))]
    lines = [
        "  ".join(cell.rjust(widths[i]) for i, cell in enumerate(row))
        for row in rows
    ]
    lines[1:1] = ["  ".join("-" * w for w in widths)]
    # second row of an era whose span carried the loop's ledger: the thread's
    # seconds by part, then the engine's by family, largest first
    out = lines[:2]
    for line, ent in zip(lines[2:], report["eras"]):
        out.append(line)
        split = "  ".join(
            f"{key}: " + " ".join(
                f"{part}={secs:.3f}"
                for part, secs in sorted(ent[key].items(), key=lambda kv: -kv[1])
            )
            for key in ("loop_s", "dispatch_s")
            if ent.get(key)
        )
        if split:
            out.append(" " * (widths[0] + 2) + split)
    return "\n".join(out)


def critical_path_table(report: Optional[dict] = None) -> str:
    """Plain-text per-era critical-path chains (CLI `trace
    --critical-path`): each era's longest blocking chain from start to
    commit, one row per merged segment, offsets relative to era start."""
    if report is None:
        report = era_report()
    lines: List[str] = []
    for ent in report["eras"]:
        cp = ent.get("critical_path") or {}
        lines.append(
            f"era {ent['era']}: critical path "
            f"{cp.get('total_s', 0.0):.3f}s "
            f"(era wall {ent['wall_s']:.3f}s)"
        )
        for sg in cp.get("segments", ()):
            lines.append(
                f"  {sg['start_s']:>10.3f}s -> {sg['end_s']:>10.3f}s  "
                f"{sg['dur_s']:>9.3f}s  {sg['kind']}:{sg['name']}"
            )
    return "\n".join(lines) if lines else "<no completed eras in trace ring>"


def capacity() -> int:
    """The rings' size now; a native engine sizes its ring from it when it
    is built. 0 = the recorder is off."""
    return _done.maxlen


def set_capacity(n: int) -> None:
    """Resize the merged span rings (keeps the newest spans) and the ring
    of every registered native engine. 0 turns the recorder off: nothing
    is recorded, held or counted as dropped, and the engines stop reading
    their clocks."""
    global _done, _native_done
    n = max(int(n), 0)
    drain_native()  # resizing an engine's ring empties it
    with _lock:
        _done = deque(_done, maxlen=n)
        _native_done = deque(_native_done, maxlen=n)
        if n == 0:
            _open.clear()
        sources = list(_native_sources.values())
    for _drain, resize in sources:
        if resize is not None:
            resize(n)


def reset_for_tests() -> None:
    global _done, _native_done, _py_dropped
    with _lock:
        _done = deque(maxlen=DEFAULT_CAPACITY)
        _open.clear()
        _native_done = deque(maxlen=DEFAULT_CAPACITY)
        _native_sources.clear()
        _py_dropped = 0
    _ledgers.own = _Ledger()
