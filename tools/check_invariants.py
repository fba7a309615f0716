#!/usr/bin/env python
"""Repo-invariant linter: static checks for the guarantees the tests assume.

Rule families D, L, P, E, M over `lachain_tpu/` (AST-based, zero
dependencies) and rule B over the checkout:

D. **Determinism** — the consensus modules (`consensus/`, `storage/trie.py`)
   must replay bit-identically: two runs from the same journal/seed may
   never diverge. Wall-clock reads
   (`time.time`, `datetime.now`), the process-global RNG (`random.*` on the
   module, unseeded `random.Random()`), entropy taps (`os.urandom`,
   `secrets.*`, `uuid.uuid4`), the builtin `hash()` (salted per process via
   PYTHONHASHSEED) and iteration over set displays/constructors (order is
   hash-salted for str/bytes elements) are all flagged. `time.monotonic` /
   `time.perf_counter` stay legal: they feed metrics and stall reports,
   never consensus values — reviewers guard that boundary, the linter
   guards the sharper one. Seeded `random.Random(seed)` is legal (the
   chaos matrices inject their seeds).

L. **Lock order** — every `threading.Lock()`/`RLock()` in the repo is
   discovered (module globals, `self.<attr>` fields — the tx-pool's 16
   shard domains collapse onto their class attribute — and dict-registry
   locks), then an acquires-while-holding graph is built from lexically
   nested `with` blocks plus a call-graph fixpoint (self-calls, same-module
   calls, and cross-module calls through imported `lachain_tpu` modules,
   e.g. the tracing/metrics singletons). Any cycle is a potential deadlock
   and fails the build. Self-edges are reported only for non-reentrant
   Lock identities (an RLock re-entered by the same thread is legal; the
   linter cannot distinguish sibling instances, so RLock classes like the
   pool shards rely on their documented no-two-shards rule).

P. **Persist-before-transmit** — a consensus payload is durable in the
   journal before any frame that carries it leaves the node. The rule is a
   pair, and only the two halves together are the guarantee:
   (1) in `consensus/`, a raw transport send (`self._send(...)`,
   `self._engine_transport(...)`) must be dominated by a journal write
   (`_durable_send` / `_native_send` / `<journal>.record`) in the same
   function: every payload is SUBMITTED to the journal's WAL before the
   transport sees it. Functions that REPLAY already-journaled bytes are
   whitelisted below, with the reason recorded next to the name.
   (2) under `network/`, every call that reaches the wire — a worker's
   `self._transport(...)`, the hub's `send_raw(...)` (a dialed connection)
   and `send_on_conn(...)` (reverse delivery to a relay client, which has
   no worker) — must be dominated by the before-wire step
   (`durable_before_wire(self._barrier)`) in the same function: the
   node's transport only queues, so a served node's `record` does not wait
   for its fsync; the wire's callers wait, once a frame, until everything
   submitted is DURABLE. Half (1) alone lets a frame outrun the fsync;
   half (2) alone has nothing to wait for. A journal whose barrier nobody
   took waits inside `record` (the simulators, the native engine: no frame
   boundary), and then half (1) is the whole rule, as it was.
   Dominance is approximated as "the dominating call appears on an
   earlier line of the same function body".
   (3) the pool's admitted rows ride the same pair. Once
   `core/tx_pool.py` submits a row without waiting (`write_batch_async`),
   the wait has to stand at both places where an admission is
   acknowledged: `core/node.py` must take `<...>.pool.frame_barrier()` (what
   it hands the network beside the journal's, so half (2) covers a frame
   that carries or follows the transaction), and under `rpc/` every
   function that calls `submit_tx(...)` must call `_after_pool_barrier(...)`
   on a later line (the answer to the client). A submit with neither is a
   later flush, not a group commit.

E. **Evidence durability** — the Byzantine-evidence counters
   (`consensus_equivocations_total`, `consensus_invalid_shares_total`) may
   only be incremented by `consensus/evidence.py`: the EvidenceStore is the
   single mint site because it persists the record (kv `write_batch` via
   `_persist`) BEFORE counting it, so a crash between persist and scrape
   under-counts but never reports evidence that is not on disk. Inside
   evidence.py the dominance is checked the same way as rule P: the
   dynamic-name `metrics.inc(metric, ...)` (the kind-mapped evidence
   counter) must appear on a later line than a `_persist`/`write_batch`
   call in the same function.

M. **Metric-name hygiene** — counters and histograms minted through
   `utils.metrics` (`inc` / `observe_hist` / `histogram`) must end in
   `_total`, `_seconds` or `_bytes`; point-in-time gauges go through
   `set_gauge` and carry no suffix by convention. Untyped names rot
   dashboards: a scraper cannot tell a monotonic counter from a
   distribution, and rate() over a gauge-shaped name is silently wrong.

B. **One benchmark** — `BENCHMARK.json` declares the benchmark: its
   `command`, and the directories (`paths`) that hold it. Outside those
   directories the tree holds no second one: no `bench*.py`, `compare.py`,
   `*_gate.json` or `MULTICHIP_*.json` (the shapes of the CPU-recorded
   scripts, gate and baselines that stood beside it until PR 29), and
   README.md quotes the declared command, so a session that starts from
   the README measures with the tool the driver runs. Directories the
   root `.gitignore` lists are not the tree. A root without
   `BENCHMARK.json` has no benchmark to be second to: the rule is silent.

Escape hatch: a line ending in `# lint-allow: <rule-id> <reason>` silences
that line for that rule. Allowed lines are counted and printed so silent
growth of the whitelist shows up in review diffs.

Exit status: 0 clean, 1 violations, 2 usage/parse errors.
Run as `python tools/check_invariants.py [repo-root]` (part of `make lint`).
"""
from __future__ import annotations

import ast
import fnmatch
import json
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Set, Tuple

# -- configuration -----------------------------------------------------------

PACKAGE = "lachain_tpu"

# rule D applies to these path prefixes/files (relative to the package root)
DETERMINISTIC_PREFIXES = ("consensus/",)
DETERMINISTIC_FILES = (
    "storage/trie.py",
    # RTT estimation feeds consensus-adjacent timeout scaling: monotonic
    # clocks are fine (injected for tests), wall clock is not
    "network/rtt.py",
)

# wall-clock attribute calls banned under rule D: module-alias . attr
WALL_CLOCK = {
    ("time", "time"),
    ("time", "time_ns"),
    ("time", "ctime"),
    ("time", "localtime"),
    ("time", "gmtime"),
    ("time", "strftime"),
    ("datetime", "now"),
    ("datetime", "utcnow"),
    ("datetime", "today"),
    ("date", "today"),
}

# entropy taps banned under rule D (module-alias . attr)
ENTROPY = {
    ("os", "urandom"),
    ("uuid", "uuid4"),
    ("uuid", "uuid1"),
}
ENTROPY_MODULES = ("secrets",)

# rule P: raw transport callees and the journal calls that must dominate them
TRANSPORT_CALLEES = ("_send", "_engine_transport")
JOURNAL_CALLEES = ("_durable_send", "_native_send", "record")
# rule P, the frame's half: the calls under network/ that put bytes on a
# socket (or hand them to a worker's transport), and the step that must
# run first
FRAME_PACKAGE = "network/"
FRAME_TRANSPORT_CALLEES = ("_transport", "send_raw", "send_on_conn")
FRAME_BARRIER_CALLEES = ("durable_before_wire",)
# rule P, the pool's rows: the submit that needs both waits, where the node
# takes the pool's barrier for its frames, and the step after an RPC submit
POOL_MODULE = "core/tx_pool.py"
POOL_SUBMIT_CALLEE = "write_batch_async"
POOL_OWNER_MODULE = "core/node.py"
POOL_BARRIER_TAKER = "frame_barrier"
POOL_ANSWER_PACKAGE = "rpc/"
POOL_INGRESS_CALLEE = "submit_tx"
POOL_ANSWER_CALLEE = "_after_pool_barrier"
# functions allowed to transport without journaling, and why. Keyed by
# function name within lachain_tpu/consensus/.
TRANSMIT_WHITELIST = {
    # replays payloads that went through _durable_send when first sent; a
    # replay of a replay must NOT be re-recorded (unbounded outbox growth)
    "replay_outbox": "re-sends already-journaled outbox entries",
    # recovery path: re-arms latches from journal records that are durable
    # by definition; it never touches the transport
    "rearm_sent": "seeds latches from already-durable journal records",
}

ALLOW_MARK = "# lint-allow:"


# -- shared helpers ----------------------------------------------------------


class Violation:
    __slots__ = ("path", "line", "rule", "msg")

    def __init__(self, path: str, line: int, rule: str, msg: str):
        self.path, self.line, self.rule, self.msg = path, line, rule, msg

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.msg}"


def _dotted(node: ast.AST) -> Optional[str]:
    """x / x.y / x.y.z -> dotted string, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _line_allowed(src_lines: List[str], lineno: int, rule: str) -> bool:
    if 1 <= lineno <= len(src_lines):
        line = src_lines[lineno - 1]
        if ALLOW_MARK in line:
            tail = line.split(ALLOW_MARK, 1)[1].strip()
            return tail.startswith(rule)
    return False


def _is_lock_ctor(node: ast.AST) -> Optional[str]:
    """threading.Lock() / threading.RLock() / Lock() -> "Lock"/"RLock"."""
    if not isinstance(node, ast.Call):
        return None
    name = _dotted(node.func)
    if name in ("threading.Lock", "Lock"):
        return "Lock"
    if name in ("threading.RLock", "RLock"):
        return "RLock"
    return None


# -- rule D: determinism -----------------------------------------------------


def check_determinism(
    relpath: str, tree: ast.Module, src_lines: List[str]
) -> List[Violation]:
    out: List[Violation] = []

    def flag(node: ast.AST, msg: str) -> None:
        if not _line_allowed(src_lines, node.lineno, "determinism"):
            out.append(Violation(relpath, node.lineno, "determinism", msg))

    # alias map so `import time as _time; _time.time()` is still caught
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"

    def base_module(name: str) -> str:
        return aliases.get(name, name).split(".")[0]

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            dotted = _dotted(node.func)
            if dotted and "." in dotted:
                head, attr = dotted.split(".")[0], dotted.split(".")[-1]
                mod = base_module(head)
                if (mod, attr) in WALL_CLOCK:
                    flag(node, f"wall-clock call {dotted}() in a "
                               "deterministic consensus module")
                elif (mod, attr) in ENTROPY or mod in ENTROPY_MODULES:
                    flag(node, f"entropy tap {dotted}() in a deterministic "
                               "consensus module")
                elif mod == "random":
                    # random.Random(seed) builds an injectable seeded RNG;
                    # everything else on the module is the process-global
                    # unseeded generator
                    if attr == "Random" and (node.args or node.keywords):
                        pass
                    else:
                        flag(node, f"process-global RNG call {dotted}() — "
                                   "inject a seeded random.Random instead")
            elif isinstance(node.func, ast.Name):
                fn = node.func.id
                if fn == "hash":
                    flag(node, "builtin hash() is salted per process "
                               "(PYTHONHASHSEED) — use a content hash")
                elif fn == "Random" and base_module(fn).startswith("random"):
                    if not (node.args or node.keywords):
                        flag(node, "unseeded random.Random() — pass a seed")
        # iteration over a set display / set() constructor: element order is
        # hash-salted for str/bytes
        iter_expr = None
        if isinstance(node, (ast.For, ast.AsyncFor)):
            iter_expr = node.iter
        elif isinstance(node, ast.comprehension):
            iter_expr = node.iter
        if iter_expr is not None:
            tgt = None
            if isinstance(iter_expr, ast.Set):
                tgt = "a set display"
            elif isinstance(iter_expr, ast.Call) and isinstance(
                iter_expr.func, ast.Name
            ) and iter_expr.func.id in ("set", "frozenset"):
                tgt = f"{iter_expr.func.id}(...)"
            if tgt:
                flag(iter_expr, f"iteration over {tgt}: order is "
                                "hash-salted — sort first")
    return out


# -- rule L: lock-order ------------------------------------------------------


class _FnInfo:
    __slots__ = ("qualname", "relpath", "acquires", "held_calls",
                 "held_acquires", "calls")

    def __init__(self, qualname: str, relpath: str):
        self.qualname = qualname
        self.relpath = relpath
        # lock ids acquired anywhere in the body
        self.acquires: Set[str] = set()
        # (held lock id, callee key, lineno)
        self.held_calls: List[Tuple[str, str, int]] = []
        # (held lock id, acquired lock id, lineno) — direct lexical nesting
        self.held_acquires: List[Tuple[str, str, int]] = []
        # callee keys invoked anywhere (for the fixpoint)
        self.calls: Set[str] = set()


class LockOrderChecker:
    """Build the acquires-while-holding graph and fail on cycles."""

    def __init__(self) -> None:
        # lock id -> kind ("Lock"/"RLock")
        self.locks: Dict[str, str] = {}
        # attr name -> {lock ids} (for resolving self.X in defining class)
        self.class_attr: Dict[Tuple[str, str, str], str] = {}
        # (relpath, global name) -> lock id
        self.module_global: Dict[Tuple[str, str], str] = {}
        # lock-returning helper: (relpath, func name) -> lock id
        self.lock_returning: Dict[Tuple[str, str], str] = {}
        self.fns: Dict[str, _FnInfo] = {}
        # callee key -> candidate fn qualnames
        self.candidates: Dict[str, List[str]] = defaultdict(list)
        # (relpath, alias) -> imported lachain_tpu module relpath
        self.imports: Dict[Tuple[str, str], str] = {}
        # edges: (held, acquired) -> example (relpath, lineno)
        self.edges: Dict[Tuple[str, str], Tuple[str, int]] = {}

    # -- pass 1: discovery ---------------------------------------------------
    def discover(self, relpath: str, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                self._record_import(relpath, node)
        for node in tree.body:
            if isinstance(node, ast.Assign):
                kind = _is_lock_ctor(node.value)
                if kind:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            lid = f"{relpath}::{tgt.id}"
                            self.locks[lid] = kind
                            self.module_global[(relpath, tgt.id)] = lid
            elif isinstance(node, ast.ClassDef):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Assign):
                        kind = _is_lock_ctor(sub.value)
                        if not kind:
                            continue
                        for tgt in sub.targets:
                            if (
                                isinstance(tgt, ast.Attribute)
                                and isinstance(tgt.value, ast.Name)
                                and tgt.value.id == "self"
                            ):
                                lid = f"{relpath}::{node.name}.{tgt.attr}"
                                self.locks[lid] = kind
                                self.class_attr[
                                    (relpath, node.name, tgt.attr)
                                ] = lid
            elif isinstance(node, ast.FunctionDef):
                # dict-registry factory: a function that creates Lock()s and
                # returns them (kernel_cache._lock_for) gets one synthetic
                # identity for the whole registry
                makes_lock = any(
                    _is_lock_ctor(s.value)
                    for s in ast.walk(node)
                    if isinstance(s, ast.Assign)
                )
                returns = any(
                    isinstance(s, ast.Return) and s.value is not None
                    for s in ast.walk(node)
                )
                if makes_lock and returns:
                    lid = f"{relpath}::{node.name}()"
                    self.locks[lid] = "Lock"
                    self.lock_returning[(relpath, node.name)] = lid

    def _record_import(self, relpath: str, node: ast.AST) -> None:
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith(PACKAGE + "."):
                    mod = a.name.replace(".", "/") + ".py"
                    self.imports[(relpath, a.asname or a.name.split(".")[-1])
                                 ] = mod
        elif isinstance(node, ast.ImportFrom) and node.level >= 0:
            # relative "from ..utils import metrics" — resolve against the
            # importing file's package position
            base: List[str]
            if node.level:
                parts = relpath.split("/")[:-1]
                base = parts[: len(parts) - (node.level - 1)]
            elif node.module and node.module.startswith(PACKAGE):
                base = node.module.split(".")
            else:
                return
            prefix = "/".join(p for p in base if p)
            if node.level and node.module:
                prefix = "/".join(
                    [prefix, node.module.replace(".", "/")]
                ).strip("/")
            for a in node.names:
                cand = (prefix + "/" + a.name + ".py").lstrip("/")
                self.imports[(relpath, a.asname or a.name)] = cand

    # -- pass 2a: register every function qualname BEFORE any body scan, so
    # cross-file call resolution is independent of file visit order
    def register_functions(self, relpath: str, tree: ast.Module) -> None:
        def walk_scope(body, qual_prefix: str) -> None:
            for node in body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qual = f"{qual_prefix}{node.name}"
                    self.fns[qual] = _FnInfo(qual, relpath)
                    self.candidates[node.name].append(qual)
                    walk_scope(node.body, qual + ".")
                elif isinstance(node, ast.ClassDef):
                    walk_scope(node.body, f"{relpath}::{node.name}.")

        walk_scope(tree.body, f"{relpath}::")

    # -- pass 2b: per-function body analysis ----------------------------------
    def analyze(self, relpath: str, tree: ast.Module,
                src_lines: List[str]) -> None:
        self._src_lines = src_lines

        def walk_scope(body, qual_prefix: str, cls: Optional[str]) -> None:
            for node in body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qual = f"{qual_prefix}{node.name}"
                    self._scan_fn(relpath, cls, node, self.fns[qual],
                                  held=[])
                    walk_scope(node.body, qual + ".", cls)
                elif isinstance(node, ast.ClassDef):
                    walk_scope(
                        node.body, f"{relpath}::{node.name}.", node.name
                    )

        walk_scope(tree.body, f"{relpath}::", None)

    def _resolve_lock(self, relpath: str, cls: Optional[str],
                      expr: ast.AST) -> Optional[str]:
        if isinstance(expr, ast.Name):
            lid = self.module_global.get((relpath, expr.id))
            if lid:
                return lid
            return None
        if isinstance(expr, ast.Attribute):
            attr = expr.attr
            if isinstance(expr.value, ast.Name) and expr.value.id == "self":
                if cls is not None:
                    lid = self.class_attr.get((relpath, cls, attr))
                    if lid:
                        return lid
            # non-self attribute (shard.lock): unique attr-name match across
            # every discovered class lock — ambiguity means no resolution
            matches = {
                lid
                for (rp, c, a), lid in self.class_attr.items()
                if a == attr
            }
            if len(matches) == 1:
                return next(iter(matches))
            return None
        if isinstance(expr, ast.Call):
            name = None
            if isinstance(expr.func, ast.Name):
                name = expr.func.id
            if name:
                lid = self.lock_returning.get((relpath, name))
                if lid:
                    return lid
        return None

    def _callee_keys(self, relpath: str, cls: Optional[str],
                     call: ast.Call) -> List[str]:
        """Resolve a call to candidate function qualnames (conservative)."""
        f = call.func
        if isinstance(f, ast.Name):
            q = f"{relpath}::{f.id}"
            return [q] if q in self.fns else []
        if isinstance(f, ast.Attribute):
            if isinstance(f.value, ast.Name):
                base = f.value.id
                if base == "self" and cls is not None:
                    q = f"{relpath}::{cls}.{f.attr}"
                    if q in self.fns:
                        return [q]
                    q2 = f"{relpath}::{f.attr}"
                    return [q2] if q2 in self.fns else []
                mod = self.imports.get((relpath, base))
                if mod is not None:
                    q = f"{mod}::{f.attr}"
                    return [q] if q in self.fns else []
        return []

    def _scan_fn(self, relpath: str, cls: Optional[str], fn,
                 info: _FnInfo, held: List[str]) -> None:
        def visit(stmts, held: List[str]) -> None:
            for node in stmts:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                    continue  # nested defs analyzed in their own scope
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    acquired: List[str] = []
                    for item in node.items:
                        lid = self._resolve_lock(
                            relpath, cls, item.context_expr
                        )
                        if lid is not None:
                            if not _line_allowed(
                                self._src_lines, node.lineno, "lock-order"
                            ):
                                info.acquires.add(lid)
                                for h in held:
                                    info.held_acquires.append(
                                        (h, lid, node.lineno)
                                    )
                            acquired.append(lid)
                    visit(node.body, held + acquired)
                    continue
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call):
                        for key in self._callee_keys(relpath, cls, sub):
                            info.calls.add(key)
                            for h in held:
                                info.held_calls.append(
                                    (h, key, sub.lineno)
                                )
                # recurse into compound statements' bodies for With nesting
                for attr in ("body", "orelse", "finalbody"):
                    sub_body = getattr(node, attr, None)
                    if sub_body and isinstance(sub_body, list):
                        # avoid double-walk: only recurse blocks that can
                        # contain With statements
                        if any(
                            isinstance(s, (ast.With, ast.AsyncWith, ast.If,
                                           ast.For, ast.While, ast.Try))
                            for s in sub_body
                        ):
                            visit(sub_body, held)
                for handler in getattr(node, "handlers", []) or []:
                    visit(handler.body, held)

        visit(fn.body, held)

    # -- pass 3: fixpoint + cycle detection ----------------------------------
    def build_edges(self) -> None:
        may: Dict[str, Set[str]] = {
            q: set(i.acquires) for q, i in self.fns.items()
        }
        changed = True
        while changed:
            changed = False
            for q, info in self.fns.items():
                cur = may[q]
                before = len(cur)
                for callee in info.calls:
                    cur |= may.get(callee, set())
                if len(cur) != before:
                    changed = True
        for q, info in self.fns.items():
            for held, lid, line in info.held_acquires:
                self.edges.setdefault((held, lid), (info.relpath, line))
            for held, callee, line in info.held_calls:
                for lid in may.get(callee, ()):
                    self.edges.setdefault((held, lid), (info.relpath, line))

    def find_cycles(self) -> List[Violation]:
        graph: Dict[str, Set[str]] = defaultdict(set)
        for (a, b), _site in self.edges.items():
            if a == b:
                # same-identity re-acquire: reentrancy, not ordering. Only a
                # non-reentrant Lock is a deadlock against ITSELF.
                if self.locks.get(a) == "Lock":
                    graph[a].add(b)
                continue
            graph[a].add(b)
        out: List[Violation] = []
        # DFS cycle detection with path recovery
        WHITE, GRAY, BLACK = 0, 1, 2
        color = {n: WHITE for n in set(graph) | {
            b for bs in graph.values() for b in bs
        }}
        stack: List[str] = []
        seen_cycles: Set[frozenset] = set()

        def dfs(n: str) -> None:
            color[n] = GRAY
            stack.append(n)
            for m in graph.get(n, ()):
                if m == n:
                    key = frozenset([n])
                    if key not in seen_cycles:
                        seen_cycles.add(key)
                        site = self.edges[(n, n)]
                        out.append(Violation(
                            site[0], site[1], "lock-order",
                            f"non-reentrant lock {n} re-acquired while "
                            "held (self-deadlock)",
                        ))
                    continue
                if color[m] == GRAY:
                    i = stack.index(m)
                    cyc = stack[i:] + [m]
                    key = frozenset(cyc)
                    if key not in seen_cycles:
                        seen_cycles.add(key)
                        site = self.edges.get(
                            (cyc[0], cyc[1])
                        ) or self.edges.get((cyc[-2], cyc[-1])) or ("?", 0)
                        out.append(Violation(
                            site[0], site[1], "lock-order",
                            "lock acquisition cycle: "
                            + " -> ".join(cyc),
                        ))
                elif color[m] == WHITE:
                    dfs(m)
            stack.pop()
            color[n] = BLACK

        for n in sorted(color):
            if color[n] == WHITE:
                dfs(n)
        return out


# -- rule P: persist-before-transmit -----------------------------------------


def check_persist_before_transmit(
    relpath: str,
    tree: ast.Module,
    src_lines: List[str],
    transport_callees: Tuple[str, ...] = TRANSPORT_CALLEES,
    dominating_callees: Tuple[str, ...] = JOURNAL_CALLEES,
    whitelist=TRANSMIT_WHITELIST,
    missing: str = (
        "a journal record (_durable_send/_native_send/journal.record)"
    ),
) -> List[Violation]:
    """Every `self.<...>.<transport_callee>(...)` must follow a call of one
    of `dominating_callees` in the same function (both halves of rule P)."""
    out: List[Violation] = []

    def scan_fn(fn) -> None:
        if fn.name in whitelist:
            return
        journal_lines: List[int] = []
        transports: List[Tuple[int, str]] = []
        # prune nested defs: their sends are their OWN responsibility
        # (scan_fn sees them via walk()), not this function's
        nested: Set[int] = set()
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                for sub in ast.walk(node):
                    if sub is not node:
                        nested.add(id(sub))
        for node in ast.walk(fn):
            if id(node) in nested:
                continue
            if not isinstance(node, ast.Call):
                continue
            name = None
            if isinstance(node.func, ast.Attribute):
                name = node.func.attr
            elif isinstance(node.func, ast.Name):
                name = node.func.id
            if name in dominating_callees:
                journal_lines.append(node.lineno)
            elif name in transport_callees:
                # only SELF-owned transports count: self._send(...),
                # self.hub.send_on_conn(...) — a nested def named _send, or
                # a local callable, is the transport's own definition, not
                # a use
                root = node.func
                while isinstance(root, ast.Attribute):
                    root = root.value
                if root is not node.func and isinstance(
                    root, ast.Name
                ) and root.id == "self":
                    transports.append((node.lineno, name))
        if not transports:
            return
        first_journal = min(journal_lines) if journal_lines else None
        for line, name in transports:
            if _line_allowed(src_lines, line, "persist-before-transmit"):
                continue
            if first_journal is None or line < first_journal:
                out.append(Violation(
                    relpath, line, "persist-before-transmit",
                    f"transport call self.{name}(...) in {fn.name}() is "
                    f"not dominated by {missing}",
                ))

    # transport-definition sites (functions ASSIGNED to self._send, e.g. the
    # _no_send stub) never transmit — skip nested defs by walking only
    # top-level functions/methods
    def walk(body) -> None:
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan_fn(node)
                walk(node.body)
            elif isinstance(node, ast.ClassDef):
                walk(node.body)

    walk(tree.body)
    return out


def _calls(node: ast.AST):
    """(call node, callee's last name) for every call under `node`."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            if isinstance(sub.func, ast.Attribute):
                yield sub, sub.func.attr
            elif isinstance(sub.func, ast.Name):
                yield sub, sub.func.id


def check_pool_rows_before_acknowledgement(
    parsed: List[Tuple[str, str, ast.Module, List[str]]],
) -> List[Violation]:
    """Rule P (3): a `write_batch_async` in the pool needs the pool's
    barrier in front of the node's frames and of the RPC answers."""
    by_rel = {rel: (relpath, tree, lines) for relpath, rel, tree, lines in parsed}
    if POOL_MODULE not in by_rel:
        return []
    relpath, tree, src_lines = by_rel[POOL_MODULE]
    submits = [
        call.lineno
        for call, name in _calls(tree)
        if name == POOL_SUBMIT_CALLEE
        and not _line_allowed(src_lines, call.lineno, "persist-before-transmit")
    ]
    if not submits:
        return []
    out: List[Violation] = []
    owner = by_rel.get(POOL_OWNER_MODULE)
    taken = owner is not None and any(
        name == POOL_BARRIER_TAKER
        and (_dotted(call.func.value) or "").split(".")[-1] == "pool"
        for call, name in _calls(owner[1])
        if isinstance(call.func, ast.Attribute)
    )
    if not taken:
        out.append(Violation(
            relpath, submits[0], "persist-before-transmit",
            f"the pool submits a row ({POOL_SUBMIT_CALLEE}) but "
            f"{PACKAGE}/{POOL_OWNER_MODULE} never takes "
            f"pool.{POOL_BARRIER_TAKER}(): no frame waits for it "
            "(durable_before_wire)",
        ))
    for rel, (rpc_path, rpc_tree, rpc_lines) in sorted(by_rel.items()):
        if not rel.startswith(POOL_ANSWER_PACKAGE):
            continue
        for fn in ast.walk(rpc_tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            calls = list(_calls(fn))
            answers = [
                c.lineno for c, name in calls if name == POOL_ANSWER_CALLEE
            ]
            for call, name in calls:
                if name != POOL_INGRESS_CALLEE or _line_allowed(
                    rpc_lines, call.lineno, "persist-before-transmit"
                ):
                    continue
                if not answers or max(answers) <= call.lineno:
                    out.append(Violation(
                        rpc_path, call.lineno, "persist-before-transmit",
                        f"{POOL_INGRESS_CALLEE}(...) in {fn.name}() is not "
                        f"followed by {POOL_ANSWER_CALLEE}(...): the answer "
                        "can leave before the admitted row is durable",
                    ))
    return out


# -- rule E: evidence durability ---------------------------------------------

EVIDENCE_MODULE = "consensus/evidence.py"
EVIDENCE_COUNTERS = (
    "consensus_equivocations_total",
    "consensus_invalid_shares_total",
)
EVIDENCE_PERSIST_CALLEES = ("_persist", "write_batch")


def _metrics_inc_name_node(node: ast.AST) -> Optional[ast.AST]:
    """metrics.inc(...) / _metrics.inc(...) -> the name argument node."""
    if not isinstance(node, ast.Call) or not isinstance(
        node.func, ast.Attribute
    ):
        return None
    if node.func.attr != "inc":
        return None
    base = _dotted(node.func.value)
    if base is None or base.split(".")[-1] not in ("metrics", "_metrics"):
        return None
    name_node: Optional[ast.AST] = node.args[0] if node.args else None
    for kw in node.keywords:
        if kw.arg == "name":
            name_node = kw.value
    return name_node


def check_evidence_durability(
    relpath: str, rel_in_pkg: str, tree: ast.Module, src_lines: List[str]
) -> List[Violation]:
    out: List[Violation] = []

    if rel_in_pkg != EVIDENCE_MODULE:
        # prong 1: nobody else mints the evidence counters
        for node in ast.walk(tree):
            name_node = _metrics_inc_name_node(node)
            if (
                isinstance(name_node, ast.Constant)
                and name_node.value in EVIDENCE_COUNTERS
            ):
                if _line_allowed(
                    src_lines, node.lineno, "evidence-durability"
                ):
                    continue
                out.append(Violation(
                    relpath, node.lineno, "evidence-durability",
                    f"evidence counter {name_node.value!r} incremented "
                    "outside consensus/evidence.py — only EvidenceStore "
                    "may count evidence (it persists the record first)",
                ))
        return out

    # prong 2: inside evidence.py, a dynamic-name inc (the kind-mapped
    # evidence counter) must be dominated by a persist call in the same
    # function. Constant-name counters (the drop counter for shed records
    # that are deliberately NOT persisted) are exempt.
    def scan_fn(fn) -> None:
        persist_lines: List[int] = []
        incs: List[int] = []
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = None
            if isinstance(node.func, ast.Attribute):
                callee = node.func.attr
            elif isinstance(node.func, ast.Name):
                callee = node.func.id
            if callee in EVIDENCE_PERSIST_CALLEES:
                persist_lines.append(node.lineno)
            name_node = _metrics_inc_name_node(node)
            if name_node is not None and not isinstance(
                name_node, ast.Constant
            ):
                incs.append(node.lineno)
        if not incs:
            return
        first_persist = min(persist_lines) if persist_lines else None
        for line in incs:
            if _line_allowed(src_lines, line, "evidence-durability"):
                continue
            if first_persist is None or line < first_persist:
                out.append(Violation(
                    relpath, line, "evidence-durability",
                    "evidence counter incremented before the record is "
                    "persisted (_persist/write_batch must dominate "
                    "metrics.inc)",
                ))

    def walk(body) -> None:
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan_fn(node)
                walk(node.body)
            elif isinstance(node, ast.ClassDef):
                walk(node.body)

    walk(tree.body)
    return out


# -- rule M: metric-name hygiene ---------------------------------------------

METRIC_SUFFIXES = ("_total", "_seconds", "_bytes")
# counters and histograms minted through these utils.metrics entry points
# must carry a typed unit suffix so the exposition stays greppable and a
# dashboard can tell a monotonic counter from a distribution by name
# alone. Gauges (set_gauge) are the documented exception: registration IS
# the gauge convention, point-in-time values carry no unit suffix.
METRIC_NAME_CALLS = ("inc", "observe_hist", "histogram")


def check_metric_names(
    relpath: str, tree: ast.Module, src_lines: List[str]
) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not isinstance(
            node.func, ast.Attribute
        ):
            continue
        if node.func.attr not in METRIC_NAME_CALLS:
            continue
        base = _dotted(node.func.value)
        # only the utils.metrics module object counts (imported as
        # `metrics` or aliased `_metrics`); foo.inc() on anything else is
        # not a metric mint
        if base is None or base.split(".")[-1] not in (
            "metrics", "_metrics"
        ):
            continue
        args = node.args
        name_node = args[0] if args else None
        for kw in node.keywords:
            if kw.arg == "name":
                name_node = kw.value
        if not isinstance(name_node, ast.Constant) or not isinstance(
            name_node.value, str
        ):
            continue  # dynamic names are reviewed by humans
        mname = name_node.value
        if mname.endswith(METRIC_SUFFIXES):
            continue
        if _line_allowed(src_lines, node.lineno, "metric-name"):
            continue
        kind = "counter" if node.func.attr == "inc" else "histogram"
        out.append(Violation(
            relpath, node.lineno, "metric-name",
            f"{kind} {mname!r} lacks a typed suffix "
            f"({'/'.join(METRIC_SUFFIXES)}); gauges belong in "
            "set_gauge()",
        ))
    return out


# -- rule B: one benchmark ---------------------------------------------------

BENCHMARK_FILE = "BENCHMARK.json"
SECOND_BENCHMARK_PATTERNS = (
    "bench*.py", "compare.py", "*_gate.json", "MULTICHIP_*.json",
)


def check_one_benchmark(root: str) -> List[Violation]:
    declared = os.path.join(root, BENCHMARK_FILE)
    if not os.path.isfile(declared):
        return []
    with open(declared, "r", encoding="utf-8") as fh:
        bench = json.load(fh)
    command = " ".join(bench["command"])
    # directories that are not the tree, by name or by path from the root
    skip = {".git"} | {p.strip("/") for p in bench["paths"]}
    ignore = os.path.join(root, ".gitignore")
    if os.path.isfile(ignore):
        with open(ignore, "r", encoding="utf-8") as fh:
            skip |= {
                line.strip().strip("/") for line in fh
                if line.strip().endswith("/")
            }

    def rel(dirpath: str, name: str) -> str:
        return os.path.relpath(os.path.join(dirpath, name), root).replace(
            os.sep, "/"
        )

    out: List[Violation] = []
    for dirpath, dirs, files in os.walk(root):
        dirs[:] = sorted(
            d for d in dirs
            if d not in skip and rel(dirpath, d) not in skip
        )
        for fn in sorted(files):
            if any(
                fnmatch.fnmatch(fn, pat)
                for pat in SECOND_BENCHMARK_PATTERNS
            ):
                out.append(Violation(
                    rel(dirpath, fn), 1, "one-benchmark",
                    f"a second benchmark beside {BENCHMARK_FILE}'s: "
                    f"measure through {command} "
                    f"(files under {', '.join(bench['paths'])}/)",
                ))
    readme = os.path.join(root, "README.md")
    text = ""
    if os.path.isfile(readme):
        with open(readme, "r", encoding="utf-8") as fh:
            text = fh.read()
    if command not in text:
        out.append(Violation(
            "README.md", 1, "one-benchmark",
            f"README.md does not quote {BENCHMARK_FILE}'s command "
            f"({command})",
        ))
    return out


# -- driver ------------------------------------------------------------------


def is_deterministic_module(relpath_in_pkg: str) -> bool:
    if relpath_in_pkg in DETERMINISTIC_FILES:
        return True
    return any(
        relpath_in_pkg.startswith(p) for p in DETERMINISTIC_PREFIXES
    )


def run(root: str) -> int:
    pkg_root = os.path.join(root, PACKAGE)
    if not os.path.isdir(pkg_root):
        print(f"check_invariants: no {PACKAGE}/ under {root}",
              file=sys.stderr)
        return 2
    violations: List[Violation] = []
    allowed_count = 0
    lock_checker = LockOrderChecker()
    parsed: List[Tuple[str, str, ast.Module, List[str]]] = []

    for dirpath, _dirs, files in sorted(os.walk(pkg_root)):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            full = os.path.join(dirpath, fn)
            rel_in_pkg = os.path.relpath(full, pkg_root).replace(
                os.sep, "/"
            )
            relpath = f"{PACKAGE}/{rel_in_pkg}"
            try:
                with open(full, "r", encoding="utf-8") as fh:
                    src = fh.read()
                tree = ast.parse(src, filename=full)
            except SyntaxError as exc:
                print(f"check_invariants: parse error in {relpath}: {exc}",
                      file=sys.stderr)
                return 2
            src_lines = src.splitlines()
            allowed_count += sum(
                1 for line in src_lines if ALLOW_MARK in line
            )
            parsed.append((relpath, rel_in_pkg, tree, src_lines))
            lock_checker.discover(relpath, tree)
            lock_checker.register_functions(relpath, tree)

    for relpath, rel_in_pkg, tree, src_lines in parsed:
        if is_deterministic_module(rel_in_pkg):
            violations += check_determinism(relpath, tree, src_lines)
        if rel_in_pkg.startswith("consensus/"):
            violations += check_persist_before_transmit(
                relpath, tree, src_lines
            )
        if rel_in_pkg.startswith(FRAME_PACKAGE):
            violations += check_persist_before_transmit(
                relpath, tree, src_lines,
                transport_callees=FRAME_TRANSPORT_CALLEES,
                dominating_callees=FRAME_BARRIER_CALLEES,
                whitelist=(),
                missing=(
                    "the journal's barrier hook "
                    "(durable_before_wire(self._barrier))"
                ),
            )
        violations += check_evidence_durability(
            relpath, rel_in_pkg, tree, src_lines
        )
        if rel_in_pkg != "utils/metrics.py":
            # the registry's own plumbing (render_text's fold cell, the
            # drop counter) is not a mint site
            violations += check_metric_names(relpath, tree, src_lines)
        lock_checker.analyze(relpath, tree, src_lines)

    violations += check_pool_rows_before_acknowledgement(parsed)
    lock_checker.build_edges()
    violations += lock_checker.find_cycles()
    violations += check_one_benchmark(root)

    for v in sorted(violations, key=lambda v: (v.path, v.line)):
        print(v)
    n_locks = len(lock_checker.locks)
    n_edges = len(lock_checker.edges)
    print(
        f"check_invariants: {len(violations)} violation(s), "
        f"{n_locks} lock identities, {n_edges} hold-acquire edges, "
        f"{allowed_count} lint-allow line(s)",
        file=sys.stderr,
    )
    return 1 if violations else 0


def main(argv: List[str]) -> int:
    root = argv[1] if len(argv) > 1 else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    return run(root)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
