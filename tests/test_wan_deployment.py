"""The deployment `hb16-wan` at a size the CPU holds (PERF.md section 4):

  * the program's LinkShaper, parsed from the configuration's string,
    against the benchmark's plain reference of the same matrix
    (perfbench/reference_wan.py) over all 240 ordered pairs of a
    16-validator striping — no frame held under its link's base delay, none
    over base + jitter x 8;
  * an N=4, f=1 fleet over loopback TCP, one validator a region, shaped
    through NetworkManager.install_wan_shaper: the committed chain executed
    again from genesis by the serial reference gives each header's state
    root, the four stores agree, every RttTracker entry is at least the two
    base delays of its pair;
  * the two trace points: network_shaped_delay_seconds_total rises by the
    delays the session returned, and the era.net_idle spans of one process
    never overlap.

Counts and orderings only: a CPU run says nothing about time.
"""
from __future__ import annotations

import asyncio
import json
import os
import random

import pytest

from lachain_tpu.crypto import ecdsa
from lachain_tpu.network.faults import FaultPlan, LinkShaper
from lachain_tpu.utils import metrics, tracing
from perfbench import reference, reference_wan

pytestmark = pytest.mark.wan

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO_ROOT, "perfbench", "configs", "hb16-wan.json")) as _fh:
    CONFIG = json.load(_fh)
NETWORK = CONFIG["network"]
REF = reference_wan.WanReference(NETWORK)
N16 = int(CONFIG["n"])
FRAMES = 2000


class _Rng:
    def __init__(self, seed):
        self._r = random.Random(seed)

    def randbelow(self, k):
        return self._r.randrange(k)


def test_the_configuration_is_the_compose_files():
    with open(os.path.join(REPO_ROOT, "docker-compose.16nodes-wan.yml")) as fh:
        compose = fh.read()
    assert f"--wan '{NETWORK['wan']}'" in compose
    assert "--n 16 --f 5" in compose and (N16, CONFIG["f"]) == (16, 5)
    assert f"--regions {','.join(NETWORK['regions'])}" in compose
    assert CONFIG["reduced"] == ["block_interval_s"] and "delay matrix" in CONFIG["assumed"]
    assert [REF.region_of(i) for i in range(5)] == ["us", "eu", "ap", "sa", "us"]


@pytest.mark.parametrize("src", range(N16))
def test_shaper_holds_every_frame_within_the_reference(src):
    """One sender's session, as its process would run it (salt = its index),
    over its 15 links and FRAMES seeded frames each."""
    session = FaultPlan(
        seed=int(CONFIG["chain_id"]), shaper=LinkShaper.parse(NETWORK["wan"])
    ).session(salt=src)
    for dst in range(N16):
        if dst == src:
            continue
        base, extra = REF.base_one_way(src, dst), REF.jitter_bound(src, dst)
        held = [session.decide(src, dst, size=200) for _ in range(FRAMES)]
        assert all(len(d) == 1 for d in held), "a shaper alone neither drops nor copies"
        delays = [d[0] for d in held]
        assert min(delays) >= base, (src, dst, min(delays), base)
        assert max(delays) <= base + extra + 1e-12, (src, dst, max(delays), base + extra)
        # the jitter is there, and not only its floor
        assert max(delays) > base + extra / 8 / 2
    assert session.stats["shaped"] == FRAMES * (N16 - 1)
    assert 0 < session.stats["bursts"] < 0.03 * FRAMES * (N16 - 1)


def test_reference_names_a_link_whose_process_lost_its_shaper():
    def report(i, lost=()):
        return {
            "shaped": 100,
            "rtt": [
                [j, 5, REF.base_one_way(i, j) + (0.0 if j in lost else REF.base_one_way(j, i)) + 0.01]
                for j in range(N16)
                if j != i
            ],
        }

    assert reference_wan.check_links(REF, [report(i) for i in range(N16)]) == []
    reports = [report(i, lost=(3,)) for i in range(N16)]
    reports[3] = {"shaped": 0, "rtt": []}
    wrong = reference_wan.check_links(REF, reports)
    assert any(w.startswith("validator 3:") and "3->{0,1,2,4," in w for w in wrong)
    # sa is 40 ms from every other region: the twelve validators outside it see it
    assert sum(w.startswith("link ") and "<->3 " in w for w in wrong) == 12
    assert not any("<->2 " in w for w in wrong)
    none = report(0)
    none["rtt"][0][1:] = [0, None]
    assert "holds no round trip to 1" in reference_wan.check_links(REF, [none] + reports[1:])[0]


def test_shaped_delay_counter_rises_by_what_the_session_returned():
    session = FaultPlan(seed=7, shaper=LinkShaper.parse(NETWORK["wan"])).session(salt=2)
    seconds = "network_shaped_delay_seconds_total"
    frames = ("fault_injected_total", {"action": "shape"})
    before = metrics.counter_value(seconds), metrics.counter_value(*frames)
    returned = sum(session.decide(2, dst, size=64)[0] for dst in (0, 1, 3, 6, 2) * 40)
    assert metrics.counter_value(*frames) - before[1] == 160  # 2 -> 2 is no link
    assert metrics.counter_value(seconds) - before[0] == pytest.approx(returned, rel=1e-9)
    assert returned > 160 * 0.002


# -- the N=4 fleet ------------------------------------------------------------------

N4, ERAS = 4, 4


def _transfers(priv, chain_id, nonce0, k):
    from lachain_tpu.core.types import Transaction, sign_transaction

    return [
        sign_transaction(
            Transaction(
                to=bytes([0x20 + (nonce0 + j) % 5]) * 20,
                value=1 + j,
                nonce=nonce0 + j,
                gas_price=1 + (nonce0 + j) % 7,
                gas_limit=21000,
            ),
            priv,
            chain_id,
        )
        for j in range(k)
    ]


@pytest.fixture(scope="module")
def shaped_fleet():
    """Four validators, four regions, ERAS eras of seeded transfers, then as
    many more (empty) as it takes every tracker to hold two round trips."""
    from lachain_tpu.core.fleet import TcpFleet

    priv = ecdsa.generate_private_key(_Rng(5))
    addr = ecdsa.address_from_public_key(ecdsa.public_key_bytes(priv))
    balances = {addr: 10**21}

    async def run():
        fleet = TcpFleet(
            n=N4,
            f=1,
            seed=27,
            txs_per_block=64,
            initial_balances=balances,
            shaper=LinkShaper.parse(NETWORK["wan"]),
            fault_seed=int(CONFIG["chain_id"]),
        )
        sent = {}
        tracing.reset_for_tests()
        await fleet.start()
        try:
            era = 0
            while era < ERAS or any(
                len(nd.network.rtt.snapshot()) < N4 - 1
                or min(e["samples"] for e in nd.network.rtt.snapshot().values()) < 2
                for nd in fleet.nodes
            ):
                assert era < ERAS + 40, "the trackers never saw two round trips"
                era += 1
                if era <= ERAS:
                    txs = _transfers(priv, fleet.chain_id, 5 * (era - 1), 5)
                    sent.update({t.hash(): t for t in txs})
                    await fleet.submit_and_settle(txs)
                await fleet.run_era(era)
            height = era
            chains = [
                [nd.block_manager.block_by_height(h) for h in range(1, height + 1)]
                for nd in fleet.nodes
            ]
            rtts = [
                {
                    j: nd.network.rtt.srtt(pub)
                    for j, pub in enumerate(fleet.public_keys.ecdsa_pub_keys)
                    if j != i
                }
                for i, nd in enumerate(fleet.nodes)
            ]
            shaped = [nd.network.hub.frame_filter.session.stats["shaped"] for nd in fleet.nodes]
        finally:
            await fleet.stop()
        return {
            "chain_id": fleet.chain_id,
            "balances": balances,
            "pubs": fleet.public_keys.ecdsa_pub_keys,
            "sent": sent,
            "chains": chains,
            "rtts": rtts,
            "shaped": shaped,
            "spans": tracing.snapshot(),
        }

    return asyncio.run(run())


def test_fleet_chain_reexecutes_from_genesis(shaped_fleet):
    chain = [
        (block, [shaped_fleet["sent"][h] for h in block.tx_hashes])
        for block in shaped_fleet["chains"][0]
    ]
    assert sum(len(txs) for _b, txs in chain) == 5 * ERAS
    assert reference.reexecute(
        shaped_fleet["chain_id"], shaped_fleet["balances"], shaped_fleet["pubs"], chain
    ) == []
    wrong, credit, nonces = reference.ledger(shaped_fleet["chain_id"], chain, shaped_fleet["sent"])
    assert wrong == [] and sum(credit.values()) == sum(
        t.tx.value for t in shaped_fleet["sent"].values()
    )
    assert list(nonces.values()) == [5 * ERAS]


@pytest.mark.parametrize("validator", range(1, N4))
def test_fleet_stores_agree(shaped_fleet, validator):
    mine = [b.hash() for b in shaped_fleet["chains"][validator]]
    assert mine == [b.hash() for b in shaped_fleet["chains"][0]] and len(mine) >= ERAS


@pytest.mark.parametrize("validator", range(N4))
def test_fleet_round_trips_are_at_least_the_two_base_delays(shaped_fleet, validator):
    assert shaped_fleet["shaped"][validator] > 0
    for peer, srtt in shaped_fleet["rtts"][validator].items():
        assert srtt >= REF.round_trip_floor(validator, peer), (validator, peer, srtt)
    reports = [
        {"shaped": s, "rtt": [[j, 2, srtt] for j, srtt in r.items()]}
        for s, r in zip(shaped_fleet["shaped"], shaped_fleet["rtts"])
    ]
    assert reference_wan.check_links(REF, reports) == []


def test_net_idle_spans_of_one_process_never_overlap(shaped_fleet):
    """Four nodes share this process's loop and each opens its own scope in
    run_era: still one span at a time, each inside some node's era."""
    idle = sorted(
        (s["start"], s["end"])
        for s in shaped_fleet["spans"]
        if s["name"] == "era.net_idle" and not s["open"]
    )
    eras = [(s["start"], s["end"]) for s in shaped_fleet["spans"] if s["name"] == "era"]
    ran = {s["args"]["era"] for s in shaped_fleet["spans"] if s["name"] == "era"}
    # a park an era, on the whole: a busy host may leave an era none (the
    # first, under one core for six test workers), the shaped links not all
    assert len(ran) >= ERAS and len(eras) >= N4 * len(ran) and len(idle) >= len(ran)
    assert all(a[1] <= b[0] for a, b in zip(idle, idle[1:]))
    assert all(any(lo <= a and b <= hi for lo, hi in eras) for a, b in idle)
    assert {s["cat"] for s in shaped_fleet["spans"] if s["name"] == "era.net_idle"} == {"net"}
    total = sum(b - a for a, b in idle)
    assert 0 < total < max(hi for _lo, hi in eras) - min(lo for lo, _hi in eras)


def test_loop_idle_leaves_the_loop_as_it_found_it():
    async def go():
        selector = asyncio.get_running_loop()._selector
        plain = selector.select
        with tracing.loop_idle("t.idle", cat="net"):
            with tracing.loop_idle("t.inner", cat="net"):
                await asyncio.sleep(0.02)
            assert selector.select != plain
            await asyncio.sleep(0.02)
        assert selector.select == plain and "select" not in vars(selector)

    tracing.reset_for_tests()
    asyncio.run(go())
    names = [s["name"] for s in tracing.snapshot() if s["name"].startswith("t.")]
    assert names and set(names) == {"t.idle"}, "the outermost scope names the spans"
