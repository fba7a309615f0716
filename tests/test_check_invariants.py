"""Tests for tools/check_invariants.py — the repo-invariant linter.

One fixture tree per violation class (written under tmp_path as a
miniature `lachain_tpu/` package), plus a clean-HEAD run proving the
real repo has zero false positives. Each evil fixture must FAIL (exit 1
with the expected rule id) and each paired good fixture must PASS —
the linter is itself a gate, so both directions are load-bearing.
"""
import importlib.util
import os
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "check_invariants", os.path.join(REPO_ROOT, "tools", "check_invariants.py")
)
ci = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ci)


def make_repo(tmp_path, files):
    """Write {relpath-under-lachain_tpu: source} and return the root."""
    for rel, src in files.items():
        p = tmp_path / "lachain_tpu" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(tmp_path)


def run_lint(tmp_path, files, capsys):
    root = make_repo(tmp_path, files)
    rc = ci.run(root)
    cap = capsys.readouterr()
    return rc, cap.out, cap.err


# -- rule D: determinism -----------------------------------------------------


def test_determinism_flags_wall_clock_entropy_hash_and_sets(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "consensus/evil_time.py": """
            import time
            import random
            import os

            def decide(payloads):
                t = time.time()
                jitter = random.random()
                salt = os.urandom(8)
                h = hash(payloads[0])
                for p in {"a", "b"}:
                    t += len(p)
                rng = random.Random()
                return t, jitter, salt, h, rng
        """,
    }, capsys)
    assert rc == 1
    assert "wall-clock call time.time()" in out
    assert "process-global RNG call random.random()" in out
    assert "entropy tap os.urandom()" in out
    assert "builtin hash()" in out
    assert "iteration over a set display" in out
    # dotted, argless random.Random() reports via the process-global rule
    assert "process-global RNG call random.Random()" in out
    assert out.count("[determinism]") == 6


def test_determinism_allows_monotonic_and_seeded_rng(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "consensus/good_time.py": """
            import time
            import random

            def measure(seed):
                t0 = time.monotonic()
                t1 = time.perf_counter()
                rng = random.Random(seed)
                for p in sorted({"a", "b"}):
                    t0 += len(p)
                return t1 - t0, rng.randrange(4)
        """,
    }, capsys)
    assert rc == 0, out


def test_determinism_sees_through_import_aliases(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "consensus/aliased.py": """
            import time as _clk
            from datetime import datetime as _dt

            def stamp():
                return _clk.time(), _dt.now()
        """,
    }, capsys)
    assert rc == 1
    assert out.count("[determinism]") == 2


def test_determinism_scoped_to_consensus_modules(tmp_path, capsys):
    # the same hazards OUTSIDE the deterministic scope are legal: metrics,
    # benchmarks and network jitter legitimately read the wall clock
    rc, out, _ = run_lint(tmp_path, {
        "rpc/service_like.py": """
            import time

            def uptime():
                return time.time()
        """,
    }, capsys)
    assert rc == 0, out


def test_lint_allow_escape_hatch_is_counted(tmp_path, capsys):
    rc, out, err = run_lint(tmp_path, {
        "consensus/escaped.py": """
            import time

            def boot_banner():
                return time.time()  # lint-allow: determinism log banner only
        """,
    }, capsys)
    assert rc == 0, out
    assert "1 lint-allow line(s)" in err


# -- rule P: persist-before-transmit -----------------------------------------


def test_transmit_without_journal_is_flagged(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "consensus/evil_send.py": """
            class Router:
                def broadcast(self, msg):
                    self._send(msg)
                    self._durable_send(msg)
        """,
    }, capsys)
    assert rc == 1
    assert "[persist-before-transmit]" in out
    assert "self._send(...) in broadcast()" in out


def test_journal_before_transmit_is_clean(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "consensus/good_send.py": """
            class Router:
                def broadcast(self, msg):
                    self._durable_send(msg)
                    self._send(msg)

                def relay(self, msg):
                    self.journal.record(msg)
                    self._engine_transport(msg)
        """,
    }, capsys)
    assert rc == 0, out


def test_replay_functions_are_whitelisted(tmp_path, capsys):
    # replay_outbox re-sends bytes that are ALREADY journaled — the
    # whitelist in the linter documents exactly this
    rc, out, _ = run_lint(tmp_path, {
        "consensus/replayer.py": """
            class Router:
                def replay_outbox(self):
                    for msg in self._outbox:
                        self._engine_transport(msg)
        """,
    }, capsys)
    assert rc == 0, out


def test_nested_def_sends_not_misattributed(tmp_path, capsys):
    # a transport call inside a nested closure belongs to the closure,
    # not the enclosing function: the enclosing fn must not be flagged
    # just because a helper it DEFINES (but may never call) transmits
    rc, out, _ = run_lint(tmp_path, {
        "consensus/nested.py": """
            class Router:
                def build(self):
                    def flush(msg):
                        self._durable_send(msg)
                        self._send(msg)
                    return flush
        """,
    }, capsys)
    assert rc == 0, out


def test_frame_without_barrier_is_flagged(tmp_path, capsys):
    # the frame's half of the pair: the final flush forgets the step, and a
    # barrier AFTER the transport protects nothing
    rc, out, _ = run_lint(tmp_path, {
        "network/worker.py": """
            class ClientWorker:
                async def _run(self):
                    await self._transport(self.peer, b"batch")
                    durable_before_wire(self._barrier)

                async def _final_flush(self):
                    await self._hub.send_raw(self.peer, b"batch")
        """,
    }, capsys)
    assert rc == 1
    assert out.count("[persist-before-transmit]") == 2
    assert "self._transport(...) in _run()" in out
    assert "self.send_raw(...) in _final_flush()" in out
    assert "barrier hook" in out


def test_reverse_delivery_without_barrier_is_flagged(tmp_path, capsys):
    # a relay client has no worker: the manager writes to its inbound
    # connection itself, and a barrier in the ENCLOSING function does not
    # cover the task that runs a loop turn later
    rc, out, _ = run_lint(tmp_path, {
        "network/manager.py": """
            class NetworkManager:
                def _send_inbound(self, conn_id, data):
                    durable_before_wire(self._barrier)

                    async def deliver():
                        await self.hub.send_on_conn(conn_id, data)

                    create_task(deliver())
        """,
    }, capsys)
    assert rc == 1
    assert out.count("[persist-before-transmit]") == 1
    assert "self.send_on_conn(...) in deliver()" in out


def test_barrier_before_frame_is_clean(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "network/worker.py": """
            class ClientWorker:
                async def _transmit(self, msgs):
                    data = self._factory.batch(msgs).encode()
                    if not durable_before_wire(self._barrier):
                        return False
                    if self._transport is None:
                        return await self._hub.send_raw(self.peer, data)
                    return await self._transport(self.peer, data)
        """,
        "network/manager.py": """
            class NetworkManager:
                def _send_inbound(self, conn_id, data):
                    async def deliver():
                        ok = durable_before_wire(self._barrier) and (
                            await self.hub.send_on_conn(conn_id, data)
                        )
        """,
        # the rule is the network's: another package's _transport is its own
        "rpc/other.py": """
            class Relay:
                async def forward(self, data):
                    await self._transport(self.peer, data)
        """,
    }, capsys)
    assert rc == 0, out


_POOL_SUBMITS = """
    class TransactionPool:
        def add(self, stx):
            self._ticket = self._kv.write_batch_async([(stx.key, stx.row)])

        def frame_barrier(self):
            return self.barrier
"""
_POOL_WAITS = """
    class TransactionPool:
        def add(self, stx):
            self._kv.put(stx.key, stx.row)
"""
_NODE_TAKES_BOTH = """
    class Node:
        def _frame_barrier(self):
            journal_barrier = self.journal.frame_barrier()
            pool_barrier = self.pool.frame_barrier()
            return lambda: (journal_barrier(), pool_barrier())
"""
_NODE_TAKES_THE_JOURNALS = """
    class Node:
        def _frame_barrier(self):
            return self.journal.frame_barrier()
"""
_RPC_ANSWERS_AFTER = """
    class RpcService:
        def eth_sendRawTransaction(self, raw):
            stx = decode(raw)
            if not self.node.submit_tx(stx):
                raise Rejected()
            return self._after_pool_barrier(stx.hash())
"""
_RPC_ANSWERS_AT_ONCE = """
    class RpcService:
        def eth_sendRawTransaction(self, raw):
            stx = decode(raw)
            self._after_pool_barrier(None)
            if not self.node.submit_tx(stx):
                raise Rejected()
            return stx.hash()

        def eth_sendTransaction(self, tx):
            self.node.submit_tx(self._build_tx(tx))
"""


@pytest.mark.parametrize(
    "pool,node,rpc,flagged",
    [
        (_POOL_SUBMITS, _NODE_TAKES_BOTH, _RPC_ANSWERS_AFTER, []),
        # a pool that waits inside add acknowledges nothing early: no pair
        (_POOL_WAITS, _NODE_TAKES_THE_JOURNALS, _RPC_ANSWERS_AT_ONCE, []),
        (
            _POOL_SUBMITS, _NODE_TAKES_THE_JOURNALS, _RPC_ANSWERS_AFTER,
            ["never takes pool.frame_barrier()"],
        ),
        (
            _POOL_SUBMITS, _NODE_TAKES_BOTH, _RPC_ANSWERS_AT_ONCE,
            [
                "submit_tx(...) in eth_sendRawTransaction() is not followed",
                "submit_tx(...) in eth_sendTransaction() is not followed",
            ],
        ),
    ],
    ids=["both-waits", "pool-waits-itself", "no-frame-wait", "no-answer-wait"],
)
def test_a_submitted_pool_row_needs_the_frame_and_the_answer(
    tmp_path, capsys, pool, node, rpc, flagged
):
    # rule P (3): once the pool submits a row without waiting, the node has
    # to take the pool's barrier for its frames and every RPC submission
    # has to answer through the barrier; a barrier BEFORE the submit, as
    # one after a transport, protects nothing
    rc, out, _ = run_lint(tmp_path, {
        "core/tx_pool.py": pool,
        "core/node.py": node,
        "rpc/service.py": rpc,
    }, capsys)
    assert rc == (1 if flagged else 0), out
    assert out.count("[persist-before-transmit]") == len(flagged)
    for text in flagged:
        assert text in out


# -- rule L: lock order ------------------------------------------------------


def test_lock_order_cycle_direct(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "consensus/evil_locks.py": """
            import threading

            _a = threading.Lock()
            _b = threading.Lock()

            def fwd():
                with _a:
                    with _b:
                        pass

            def rev():
                with _b:
                    with _a:
                        pass
        """,
    }, capsys)
    assert rc == 1
    assert "[lock-order]" in out
    assert "cycle" in out


def test_lock_order_cycle_through_call_graph(tmp_path, capsys):
    # the reverse edge only exists interprocedurally: rev() holds _b and
    # CALLS helper(), which acquires _a — the fixpoint must find it
    rc, out, _ = run_lint(tmp_path, {
        "consensus/evil_calls.py": """
            import threading

            _a = threading.Lock()
            _b = threading.Lock()

            def fwd():
                with _a:
                    with _b:
                        pass

            def helper():
                with _a:
                    pass

            def rev():
                with _b:
                    helper()
        """,
    }, capsys)
    assert rc == 1
    assert "[lock-order]" in out


def test_lock_order_consistent_nesting_is_clean(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "consensus/good_locks.py": """
            import threading

            _a = threading.Lock()
            _b = threading.Lock()

            def one():
                with _a:
                    with _b:
                        pass

            def two():
                with _a:
                    with _b:
                        pass
        """,
    }, capsys)
    assert rc == 0, out


def test_self_deadlock_on_plain_lock_only(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "consensus/self_lock.py": """
            import threading

            _plain = threading.Lock()

            def oops():
                with _plain:
                    with _plain:
                        pass
        """,
        "consensus/self_rlock.py": """
            import threading

            _re = threading.RLock()

            def fine():
                with _re:
                    with _re:
                        pass
        """,
    }, capsys)
    assert rc == 1
    assert "self-deadlock" in out
    # the RLock re-entry must NOT appear
    assert "_re" not in out


def test_cross_module_lock_edges_via_imports(tmp_path, capsys):
    # metrics-singleton pattern: consensus code holds its own lock and
    # calls into an imported lachain_tpu module that takes another lock;
    # that module reverses the order -> cycle spans two files
    rc, out, _ = run_lint(tmp_path, {
        "consensus/caller.py": """
            import threading
            from lachain_tpu.observability import metrics_like

            _era = threading.Lock()

            def report():
                with _era:
                    metrics_like.observe(1)
        """,
        "observability/metrics_like.py": """
            import threading
            from lachain_tpu.consensus import caller

            _reg = threading.Lock()

            def observe(v):
                with _reg:
                    pass

            def poke():
                with _reg:
                    caller.report()
        """,
    }, capsys)
    assert rc == 1
    assert "[lock-order]" in out


# -- driver behaviour --------------------------------------------------------


def test_parse_error_is_usage_error(tmp_path, capsys):
    rc, _, err = run_lint(tmp_path, {
        "consensus/broken.py": "def broken(:\n",
    }, capsys)
    assert rc == 2
    assert "parse error" in err


def test_missing_package_root(tmp_path, capsys):
    rc = ci.run(str(tmp_path / "nowhere"))
    capsys.readouterr()
    assert rc == 2


@pytest.mark.slow
def test_clean_head_has_zero_violations(capsys):
    # the gate that `make lint` enforces: the real repo is clean
    rc = ci.run(REPO_ROOT)
    cap = capsys.readouterr()
    assert rc == 0, cap.out
    assert "0 violation(s)" in cap.err


# -- rule M: metric-name hygiene ---------------------------------------------


def test_metric_names_require_typed_suffix(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "rpc/evil_metrics.py": """
            from ..utils import metrics

            def handle():
                metrics.inc("requests_served")
                metrics.observe_hist("request_latency", 0.1)
                metrics.histogram("queue_wait")
        """,
    }, capsys)
    assert rc == 1
    assert out.count("[metric-name]") == 3
    assert "counter 'requests_served'" in out
    assert "histogram 'request_latency'" in out
    assert "_total/_seconds/_bytes" in out


def test_metric_names_with_suffix_and_gauges_are_clean(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "rpc/good_metrics.py": """
            from ..utils import metrics as _metrics

            def handle(peer):
                _metrics.inc("requests_served_total")
                _metrics.observe_hist("request_latency_seconds", 0.1)
                _metrics.observe_hist("reply_size_bytes", 512.0)
                # gauges are the documented exception: no suffix required
                _metrics.set_gauge("pool_depth", 7.0)
                # dynamic names are reviewed by humans, not the linter
                _metrics.inc("peer_" + peer)
                # .inc on a non-metrics object is not a metric mint
                peer.inc("whatever")
        """,
    }, capsys)
    assert rc == 0, out


def test_evidence_counter_minted_outside_evidence_module(tmp_path, capsys):
    # the evidence counters imply "a record is on disk"; a module bumping
    # them directly would break that contract even with correct values
    rc, out, _ = run_lint(tmp_path, {
        "consensus/evil_evidence.py": """
            from ..utils import metrics

            def convict(sender):
                metrics.inc(
                    "consensus_equivocations_total", labels={"proto": "coin"}
                )
        """,
    }, capsys)
    assert rc == 1
    assert "[evidence-durability]" in out
    assert "outside consensus/evidence.py" in out


def test_evidence_count_before_persist_is_flagged(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "consensus/evidence.py": """
            from ..utils import metrics

            class EvidenceStore:
                def _record(self, rec, metric):
                    metrics.inc(metric, labels={"proto": rec.proto})
                    self._persist(rec)
        """,
    }, capsys)
    assert rc == 1
    assert "[evidence-durability]" in out
    assert "before the record is persisted" in out


def test_evidence_persist_then_count_is_clean(tmp_path, capsys):
    rc, out, _ = run_lint(tmp_path, {
        "consensus/evidence.py": """
            from ..utils import metrics

            class EvidenceStore:
                def _record(self, rec, metric):
                    if self._full():
                        # shed records are deliberately NOT persisted; the
                        # constant-name drop counter is exempt from the
                        # dominance rule
                        metrics.inc("consensus_evidence_dropped_total")
                        return False
                    self._persist(rec)
                    metrics.inc(metric, labels={"proto": rec.proto})
                    return True
        """,
    }, capsys)
    assert rc == 0, out


def test_metric_name_lint_allow_escape(tmp_path, capsys):
    rc, out, err = run_lint(tmp_path, {
        "rpc/allowed_metrics.py": """
            from ..utils import metrics

            def handle():
                metrics.observe_hist(  # lint-allow: metric-name dimensionless slot count
                    "flush_slots", 4.0
                )
        """,
    }, capsys)
    assert rc == 0, out
    assert "1 lint-allow line(s)" in err


# -- rule B: one benchmark ---------------------------------------------------


def test_one_benchmark_clean_head():
    # no script, gate or baseline beside perfbench/, and README.md quotes
    # BENCHMARK.json's command: the real tree, without the slow AST passes
    assert [str(v) for v in ci.check_one_benchmark(REPO_ROOT)] == []


@pytest.mark.parametrize("planted", [
    "bench.py",
    "benchmarks/bench_consensus_sim.py",
    "benchmarks/compare.py",
    "benchmarks/BENCH_sim_gate.json",
    "MULTICHIP_r01.json",
])
def test_second_benchmark_is_flagged(tmp_path, capsys, planted):
    import json

    (tmp_path / "BENCHMARK.json").write_text(json.dumps(
        {"command": ["python3", "perfbench/run.py"], "paths": ["perfbench"]}
    ))
    (tmp_path / "README.md").write_text(
        "measure with `python3 perfbench/run.py --workload <cell>`\n"
    )
    (tmp_path / ".gitignore").write_text("_checkout/\n*.so\n")
    # the benchmark's own directory and a git-ignored copy of another
    # commit may hold such names; neither is the tree
    for own in ("perfbench/compare.py", "_checkout/parent/bench.py"):
        (tmp_path / own).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / own).write_text("")
    files = {"utils/ok.py": "X = 1\n"}
    assert run_lint(tmp_path, files, capsys)[0] == 0
    (tmp_path / planted).parent.mkdir(parents=True, exist_ok=True)
    (tmp_path / planted).write_text("")
    rc, out, _ = run_lint(tmp_path, files, capsys)
    assert rc == 1
    assert out.count("[one-benchmark]") == 1 and planted in out
    (tmp_path / planted).unlink()
    (tmp_path / "README.md").write_text("python benchmarks/bench_x.py\n")
    rc, out, _ = run_lint(tmp_path, files, capsys)
    assert rc == 1 and "README.md does not quote" in out
