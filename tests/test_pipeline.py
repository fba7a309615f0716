"""Consensus era pipelining (core/devnet.py windowed scheduler +
consensus/native_rt.py per-era engines).

The pipeline's whole correctness claim is "same blocks, sooner": era e+1's
front (propose/encrypt/RBC/BA/coin/TPKE verify-combine) overlaps era e's
tail (sign/flood/verify/produce/commit), while commits stay strictly
sequential. Every test here checks an invariant that claim rests on:
block-hash identity against the sequential run, bit-identity across runs
under seeded faults, journal GC holding the full overlap window, crash
recovery replaying BOTH in-flight eras without self-equivocation, and
stall reports naming the wedged era.
"""
import os
import signal
import subprocess
import sys
import textwrap

import pytest

from lachain_tpu.consensus import messages as M
from lachain_tpu.consensus.simulator import DeliveryMode
from lachain_tpu.core.devnet import Devnet
from lachain_tpu.core.types import Transaction, sign_transaction
from lachain_tpu.crypto import ecdsa

from tests.test_consensus import SeededRng, keys_for

pytestmark = pytest.mark.pipeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mk_devnet(txs=12, n=4, f=1, mode=DeliveryMode.TAKE_FIRST, **kw):
    users = [ecdsa.generate_private_key(SeededRng(40 + i)) for i in range(4)]
    balances = {
        ecdsa.address_from_public_key(ecdsa.public_key_bytes(u)): 10**21
        for u in users
    }
    net = Devnet(
        n, f, seed=11, txs_per_block=txs, initial_balances=balances,
        engine="native", mode=mode, **kw,
    )
    nonce = [0] * len(users)
    for k in range(txs):
        u = k % len(users)
        stx = sign_transaction(
            Transaction(
                to=b"\x42" * 20,
                value=1,
                nonce=nonce[u],
                gas_price=1,
                gas_limit=21000,
            ),
            users[u],
            net.chain_id,
        )
        assert net.submit_tx(stx)
        nonce[u] += 1
    return net


@pytest.mark.slow
@pytest.mark.parametrize("n,f", [(7, 2), (10, 3)])
def test_pipeline_on_off_identical_blocks(n, f):
    """The headline determinism contract: a pipelined run (window=1) must
    produce BIT-IDENTICAL block hashes to the sequential run of the same
    devnet — overlap may only change wall-clock, never content."""
    hashes = {}
    for window in (0, 1):
        net = _mk_devnet(txs=12, n=n, f=f, pipeline_window=window)
        hashes[window] = [b.hash() for b in net.run_eras(1, 3)]
    assert hashes[1] == hashes[0]


def test_pipeline_two_run_bit_identity_faultplan_window2():
    """Two pipelined runs (window=2, so up to three eras in flight) under
    the native engine's expressible FaultPlan subset (duplicate + reorder)
    and adversarial delivery: same seed -> bit-identical blocks and
    delivery counts. Catches any nondeterminism the overlap could smuggle
    in (cross-era batcher mixing, overlay races, per-era seed drift)."""
    from lachain_tpu.network.faults import FaultPlan

    runs = []
    for _ in range(2):
        net = _mk_devnet(
            txs=12,
            mode=DeliveryMode.TAKE_RANDOM,
            pipeline_window=2,
            fault_plan=FaultPlan(seed=9, duplicate=0.04, reorder=0.5),
        )
        blocks = [b.hash() for b in net.run_eras(1, 4)]
        runs.append((blocks, net.net.delivered_count))
    assert runs[0] == runs[1]


def test_pipeline_stall_report_names_stuck_era():
    """A wedged era must fail loudly AND diagnosably: with 2 of 4
    validators muted (quorum lost), the scheduler's pump raises a stall
    report naming the stuck era, its lane, the in-flight window, and
    per-validator engine state."""
    net = _mk_devnet(txs=8, pipeline_window=1)
    net.net.mute(2)
    net.net.mute(3)
    with pytest.raises(RuntimeError) as exc:
        net.run_eras(1, 2, max_messages=200_000)
    msg = str(exc.value)
    assert "era 1" in msg
    assert "validator 0" in msg


def test_pipeline_depth_gauge_and_overlap_report():
    """Satellite observability contract: the consensus_pipeline_depth
    gauge rises during the run and returns to 0, and era_report attributes
    a positive overlap_s to eras whose windows genuinely overlapped (and
    zero when run sequentially)."""
    from lachain_tpu.utils import metrics, tracing

    tracing.reset_for_tests()
    net = _mk_devnet(txs=8, pipeline_window=1)
    net.run_eras(1, 3)
    assert metrics.gauge_value("consensus_pipeline_depth") == 0
    report = {e["era"]: e for e in tracing.era_report()["eras"]}
    assert sorted(report) == [1, 2, 3]
    # era 2's window overlaps era 1's tail and era 3's front
    assert report[2]["overlap_s"] > 0.0
    assert all("overlap_s" in e for e in report.values())
    # the table surfaces the new column
    assert "overlap_s" in tracing.era_report_table()

    tracing.reset_for_tests()
    net2 = _mk_devnet(txs=8)
    net2.run_eras(1, 2)
    for ent in tracing.era_report()["eras"]:
        assert ent["overlap_s"] == 0.0


def test_pipeline_journal_gc_holds_window():
    """Journal GC must retain every era that can still overlap an
    uncommitted one: with window=w, committing era c prunes only eras
    below c+1-w. After a full run the journals hold exactly the last w
    eras — pruning earlier would orphan replay state a crashed peer may
    still request; pruning later would leak."""
    from lachain_tpu.consensus.journal import ConsensusJournal
    from lachain_tpu.storage.kv import MemoryKV

    for window, kept in ((1, {4}), (2, {3, 4})):
        journals = [ConsensusJournal(MemoryKV()) for _ in range(4)]
        net = _mk_devnet(
            txs=8,
            mode=DeliveryMode.TAKE_RANDOM,
            pipeline_window=window,
            journals=journals,
        )
        net.run_eras(1, 4)
        eras_left = {e for e, _s, _t, _d in journals[0].entries()}
        assert eras_left == kept, (window, eras_left)


def test_node_watchdog_names_window_floor_era(caplog):
    """With pipelining active the node watchdog must blame the OLDEST
    uncommitted era (the router's window_floor) — commits are sequential,
    so that is the era actually wedging the chain, not the newest one the
    router has admitted."""
    import logging
    from types import SimpleNamespace

    from lachain_tpu.core.node import Node

    router = SimpleNamespace(
        era=5, window_floor=3, result_of=lambda pid: None
    )
    fake = SimpleNamespace(
        _native_watch=("", 0.0, 0), effective_stall_timeout=1.0,
        pipeline_window=2,
    )
    assert Node._check_native_stall(fake, router, "stuck-state", 0.0) == 0
    with caplog.at_level(logging.WARNING, logger="lachain_tpu.core.node"):
        strikes = Node._check_native_stall(fake, router, "stuck-state", 5.0)
    assert strikes == 1
    assert "era 3" in caplog.text

    # window off: the legacy single-era attribution stays
    caplog.clear()
    fake2 = SimpleNamespace(
        _native_watch=("", 0.0, 0), effective_stall_timeout=1.0,
        pipeline_window=0,
    )
    Node._check_native_stall(fake2, router, "stuck-state", 0.0)
    with caplog.at_level(logging.WARNING, logger="lachain_tpu.core.node"):
        Node._check_native_stall(fake2, router, "stuck-state", 5.0)
    assert "era 5" in caplog.text


def test_pipeline_window_config_knob():
    """blockchain.pipelineWindow parses into the typed section and
    defaults to 0 (sequential) for existing configs."""
    from lachain_tpu.core.config import CURRENT_VERSION, NodeConfig

    cfg = NodeConfig.from_dict(
        {"version": CURRENT_VERSION, "blockchain": {"pipelineWindow": 2}}
    )
    assert cfg.blockchain.pipeline_window == 2
    assert (
        NodeConfig.from_dict(
            {"version": CURRENT_VERSION}
        ).blockchain.pipeline_window
        == 0
    )


_CRASH_CHILD = textwrap.dedent(
    """
    import os, signal, sys
    sys.path.insert(0, {repo!r})
    from lachain_tpu.consensus import messages as M
    from lachain_tpu.consensus.journal import ConsensusJournal
    from lachain_tpu.consensus.simulator import DeliveryMode
    from lachain_tpu.storage.lsm import LsmKV
    from tests.test_pipeline import _mk_devnet

    base = {base!r}
    journals = [
        ConsensusJournal(LsmKV(os.path.join(base, "j%d" % i)))
        for i in range(4)
    ]
    net = _mk_devnet(
        txs=8, mode=DeliveryMode.TAKE_RANDOM, pipeline_window=1,
        journals=journals,
    )
    # drive the scheduler primitives by hand so the kill lands at a
    # DETERMINISTIC mid-window point: both eras' fronts complete (their
    # coin/decrypt sends journaled persist-before-transmit), NEITHER era
    # committed, no GC run
    net.net.pipeline_begin()
    for era in (1, 2):
        net.net.open_era(era)
        pid = M.RootProtocolId(era=era)
        for i in range(4):
            net.net.post_request(i, pid, None)
        net.net.run_front(era)
        if era == 1:
            txs = net._decided_txs(1)
            for node in net.nodes:
                node.producer.pipeline_overlay_push(1, txs, net.chain_id)
    print("MID-WINDOW", flush=True)
    os.kill(os.getpid(), signal.SIGKILL)
    """
)


@pytest.mark.slow
@pytest.mark.crash
def test_pipeline_sigkill_mid_window_replays_both_eras(tmp_path):
    """Crash durability across the overlap window: SIGKILL a process with
    TWO eras in flight (both fronts complete, neither committed). The
    durable journals must come back holding BOTH eras' sends, and a
    restarted validator must substitute the RECORDED bytes for every
    replayed slot in both eras — re-deriving (self-equivocation) on
    either in-flight era would let an adversary collect two signed
    versions of the same share."""
    from lachain_tpu.consensus import messages as M
    from lachain_tpu.consensus.journal import ConsensusJournal, send_slot
    from lachain_tpu.consensus.native_rt import NativeSimulatedNetwork
    from lachain_tpu.network import wire
    from lachain_tpu.storage.lsm import LsmKV

    child = tmp_path / "child.py"
    child.write_text(
        _CRASH_CHILD.format(repo=REPO, base=str(tmp_path))
    )
    proc = subprocess.run(
        [sys.executable, str(child)],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == -signal.SIGKILL, proc.stderr
    assert "MID-WINDOW" in proc.stdout

    # reopen the durable journals (LSM WAL recovery) — both in-flight
    # eras' sends must have survived the kill
    journals = [
        ConsensusJournal(LsmKV(str(tmp_path / f"j{i}"))) for i in range(4)
    ]
    eras_found = {e for e, _s, _t, _d in journals[0].entries()}
    assert {1, 2} <= eras_found, eras_found

    recorded = {}
    for era, _seq, _target, data in journals[0].entries():
        slot = send_slot(wire.decode_payload(data))
        if slot is not None:
            recorded[(era, slot)] = data
    assert any(e == 1 for e, _ in recorded)
    assert any(e == 2 for e, _ in recorded)

    # restart: fresh native net over the same journals, latches re-armed
    pub, privs = keys_for(4, 1)
    net2 = NativeSimulatedNetwork(
        pub, privs, era=1, seed=99, mode=DeliveryMode.TAKE_RANDOM,
        journals=journals,
    )
    try:
        r0 = net2.routers[0]
        for era, _seq, target, data in journals[0].entries():
            r0.rearm_sent(era, target, data)
        checked = {1: 0, 2: 0}
        for (era, slot), data in recorded.items():
            stale = wire.decode_payload(data)
            if isinstance(stale, M.CoinMessage):
                fresh = M.CoinMessage(
                    coin=stale.coin, share=bytes(len(stale.share))
                )
            elif isinstance(stale, M.DecryptedMessage):
                fresh = M.DecryptedMessage(
                    hb=stale.hb,
                    share_id=stale.share_id,
                    payload=bytes(len(stale.payload)),
                )
            else:
                continue
            sent = r0._native_send(fresh)
            assert wire.encode_payload(sent) == data, (
                f"self-equivocation on {(era, slot)} after mid-window kill"
            )
            checked[era] += 1
        assert checked[1] > 0 and checked[2] > 0, checked
    finally:
        net2.close()
        for j in journals:
            j._kv.close()
