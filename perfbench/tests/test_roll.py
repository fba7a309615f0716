"""The cell `hb7-roll.open200` rehearsed on the CPU: reference_roll's schedule
and quorum against hand-made good and bad logs, the cell's entries and
files, and the whole cell at N=4 through harness.Rehearsal with a real
SIGKILL and a restart on the same store. Counts and `correct` only: a CPU
run says nothing about time."""
import json
import time

import pytest

from perfbench import reductions, reference_roll, spec
from perfbench.harness import Rehearsal, run_cell
from perfbench.tests.test_rehearsal import TINY

CELL = "hb7-roll.open200"
BENCH = spec.load_benchmark()
NEW = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
ROLL = spec.load_cell(CELL).config["roll"]
SEED = 2147495005  # 1 + (seed mod 6) = 2
W0, W1 = 100.0, 151.0


def _entry(k, victim, t_kill, back_after=3.0, **over):
    e = {
        "victim": victim, "old_pid": 1000 + k, "new_pid": 2000 + k,
        "t_kill": t_kill, "t_reaped": t_kill + 0.002, "t_spawned": t_kill + 0.01,
        "t_listening": t_kill + 1.2, "t_rejoined": t_kill + back_after,
    }
    e.update(over)
    return e


def _good_log():
    """Victims 2, 3, 4, 5 back to back from 2 s into the window."""
    log, t = [], W0 + 2.0
    for k, victim in enumerate((2, 3, 4, 5)):
        log.append(_entry(k, victim, t))
        t = log[-1]["t_rejoined"] + 0.05
    return log


def test_the_reference_derives_the_schedule_from_the_configuration_alone():
    ref = reference_roll.RollReference(ROLL, 7, SEED)
    assert [ref.victim(k) for k in range(8)] == [2, 3, 4, 5, 6, 1, 2, 3]
    assert 0 not in {reference_roll.RollReference(ROLL, 7, s).victim(k)
                     for s in range(12) for k in range(12)}
    assert not ref.may_kill(W0 + 1.99, W0, W1) and ref.may_kill(W0 + 2.0, W0, W1)
    assert ref.may_kill(W1 - 1.0, W0, W1) and not ref.may_kill(W1 - 0.99, W0, W1)
    assert reference_roll.check_schedule(ref, _good_log(), W0, W1) == []
    with open(reference_roll.__file__, encoding="utf-8") as fh:
        source = fh.read()
    assert "drivers" not in source.split('"""', 2)[2], "it imports nothing of the driver"


def _two_at_once(log):
    log[1]["t_kill"] = log[0]["t_rejoined"] - 0.5  # killed while 2 was coming back
    return "before validator 2 was back"


def _never_back(log):
    log[1]["t_rejoined"] = None
    return "still out"


def _wrong_order(log):
    log[1]["victim"] = 5
    return "out of order"


def _validator_0(log):
    log[0]["victim"] = 0
    return "never kills"


def _too_early(log):
    log[0]["t_kill"] = W0 + 1.0
    return "outside"


def _in_the_last_second(log):
    log.append(_entry(4, 6, W1 - 0.5))
    return "outside"


def _spawned_before_reaped(log):
    log[2]["t_spawned"] = log[2]["t_reaped"] - 0.001
    return "before the old one was reaped"


def _same_process(log):
    log[2]["new_pid"] = log[2]["old_pid"]
    return "same process"


def _a_pause(log):
    for e in log[2:]:
        for key in ("t_kill", "t_reaped", "t_spawned", "t_listening", "t_rejoined"):
            e[key] += 5.0
    return "dwell_s is 0"


def _too_few(log):
    del log[2:]
    return "3 needed"


@pytest.mark.parametrize(
    "spoil",
    [_two_at_once, _never_back, _wrong_order, _validator_0, _too_early,
     _in_the_last_second, _spawned_before_reaped, _same_process, _a_pause, _too_few],
    ids=lambda f: f.__name__.strip("_"),
)
def test_a_bad_log_is_found(spoil):
    log = _good_log()
    what = spoil(log)
    ref = reference_roll.RollReference(ROLL, 7, SEED)
    wrong = reference_roll.check_schedule(ref, log, W0, W1)
    assert any(what in w for w in wrong), wrong


def test_a_multisig_short_of_the_quorum_is_found():
    from lachain_tpu.crypto import ecdsa
    from lachain_tpu.crypto.hashes import keccak256
    from perfbench.traffic import SeededRng

    n, f = 7, 2
    privs = [ecdsa.generate_private_key(SeededRng(90 + i)) for i in range(n)]
    pubs = [ecdsa.public_key_bytes(p) for p in privs]
    h = keccak256(b"header")
    sig = lambda i, what=h: (i, ecdsa.sign_hash(privs[i], what))
    good = [sig(i) for i in (0, 1, 3, 4, 6)]
    assert reference_roll.check_multisigs(n, f, pubs, [(9, h, good)]) == []
    bad = {
        "four": good[:4],
        "one twice": good[:4] + [good[0]],
        "another header's": good[:4] + [sig(6, keccak256(b"other"))],
        "under another's index": good[:4] + [(5, good[4][1])],
        "no validator": good[:4] + [(7, good[4][1])],
    }
    for name, signatures in bad.items():
        wrong = reference_roll.check_multisigs(n, f, pubs, [(9, h, signatures)])
        assert wrong and "4 valid" in wrong[0], name


def test_the_entries_are_the_ones_the_issue_lists():
    names = [
        "restart_rejoin_p50_s", "restarts_per_window", "recover_spawn_s",
        "recover_store_open_s", "recover_journal_s", "recover_connect_s",
        "recover_catch_up_s", "recover_rejoin_s", "sync_blocks_per_restart",
        "ba_rounds_per_era", "acs_slots_rejected_per_era",
        "peer_reconnects_per_era", "sync_blocks_served_per_era",
    ]
    assert [m["name"] for m in NEW] == names
    assert [m["name"] for m in BENCH["per_layer"]][-len(names):] == names
    assert {m["layer"] for m in NEW[:9]} == {"recovery"}
    assert all(m["moves"] == "era_p50_s" for m in NEW)
    config = next(c for c in BENCH["configs"] if c["name"] == "hb7-roll")
    assert config["reduced"] == ["block_interval_s"] and len(config["source"]) <= 200
    assert all(w in config["source"] for w in ("BLOCKBENCH", "DEPLOY.md", "config_mainnet.json"))
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("hb7-roll", "open-200", 1)
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {
        "era_p50_s", "commit_p50_s", "commit_p95_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    for m in BENCH["per_layer"]:
        if "hb7.quiet" in m.get("workloads", []):
            assert m["name"] in reported, "every list that holds hb7.quiet holds the cell"
        assert m["moves"] in {e["name"] for e in cell.end_to_end} or CELL not in m.get("workloads", [])
    peers_cfg = spec._read_json(spec.BENCH_DIR / "configs" / "hb7-peers.json")
    same = ("n", "f", "txs_per_block", "chain_id", "block_interval_s", "backend", "warm",
            "drain_seconds_max", "storage", "network", "reduced")
    assert all(cell.config[k] == peers_cfg[k] for k in same)
    assert cell.traffic["rate_per_s"] == 200 and cell.traffic["loop"] == "open"
    quiet = spec._read_json(spec.BENCH_DIR / "traffic" / "quiet.json")
    assert all(cell.traffic[k] == quiet[k] for k in ("burst", "accounts", "tx", "gaps"))
    assert (ROLL["signal"], ROLL["gate"], ROLL["dwell_s"], ROLL["first_kill_s"]) == (
        "SIGKILL", "consensus", 0, 2.0)
    assert ROLL["victims"]["never"] == [0] and len(cell.config["guarantees"]) == 7


@pytest.mark.parametrize("entry", NEW, ids=lambda m: m["name"])
def test_entry_has_its_file(entry):
    metric = spec.load_layer_metric(entry["name"])
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert metric[key] == entry[key], key
    assert metric["reduction"] in reductions.REDUCTIONS and len(metric["why"]) > 40


@pytest.fixture(scope="module")
def lines():
    """The cell at N=4 (one of three children always down: N-f = 3 go on),
    ten seconds, untraced and traced, one restart asked for."""
    config = {**TINY, "txs_per_block": 100, "roll": {**ROLL, "min_restarts": 1}}
    out = {}
    for trace in (0, 1):
        line = run_cell(
            CELL, SEED, 10.0, bool(trace), time.monotonic(),
            rehearsal=Rehearsal(config=config, traffic={"rate_per_s": 40}),
        )
        out[trace] = json.loads(json.dumps(line))
    return out


def test_the_whole_cell_with_a_kill_and_a_restart_is_correct(lines):
    line = lines[0]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 100
    assert set(line["metrics"]) == {"era_p50_s", "commit_p50_s", "commit_p95_s", "setup_s"}


def test_every_new_metric_has_a_reading_and_the_phases_add_up(lines):
    line = lines[1]
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert {m["name"] for m in NEW} <= set(got)
    assert got["restarts_per_window"] >= 1 and got["sync_blocks_per_restart"] >= 1
    assert got["ba_rounds_per_era"] >= 4 and got["acs_slots_rejected_per_era"] > 0
    phases = sum(got[k] for k in (
        "recover_spawn_s", "recover_store_open_s", "recover_journal_s",
        "recover_connect_s", "recover_catch_up_s", "recover_rejoin_s"))
    # medians of a handful of restarts: the sum of medians is near the
    # median of sums, not equal to it
    assert phases == pytest.approx(got["restart_rejoin_p50_s"], rel=0.35)
