"""Driver `share`: one validator's own era in a committee of N. Validator 0
runs in the benchmark's process with its own store, pool, producer and era
batchers (core/devnet.CommitteeValidator); its N-1 peers are the honest
committee script (lachain_tpu/consensus/committee_script.py), whose tables
are built in set-up, in worker processes while the kernels compile.
Validator 0's clients send it the cell's traffic; the
peers propose transfers from accounts of their own. Load is handed over
between eras as in the `devnet` driver. A block is committed, from the
client's side, when validator 0 has persisted it.
"""
from __future__ import annotations

import math
import os
import time

from .. import reference, reference_share
from ..traffic import BlockSeen, Load
from . import devnet


class _LoadAfterTables(Load):
    """The load's clock starts once the script's tables are built (the
    `devnet` driver's warm() starts it after the kernel shapes, which the
    tables are built beside), so that no backlog piles up while set-up
    waits for them."""

    def __init__(self, script, bench, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._script, self._bench = script, bench

    def start(self) -> None:
        waited = self._script.join()
        self._bench.say(f"committee script: tables ready, waited {waited:.1f} s")
        super().start()


class Driver(devnet.Driver):
    def __init__(self, cell, bench):
        super().__init__(cell, bench)
        self.script = None
        self.slots = {}  # era -> the slots its ACS held
        self.coins = {}  # era -> {(agreement, epoch): value} validator 0 used
        self.era_s = []  # seconds of each era run so far

    # -- set-up -------------------------------------------------------------------
    def setup(self) -> None:
        from lachain_tpu.consensus.committee_script import CommitteeScript
        from lachain_tpu.core.devnet import CommitteeValidator, devnet_keys

        cfg, bench, seed = self.cfg, self.bench, self.cell.seed
        n, f = int(cfg["n"]), int(cfg["f"])
        script = cfg["script"]
        # the tables cover the warm eras, the most eras the window holds at
        # the fastest era measured, and the drain
        window_eras = math.ceil(float(script["window_s"]) / float(script["era_floor_s"]))
        eras = int(cfg["warm_eras"]) + window_eras + int(cfg["drain_eras_max"])
        t0 = time.monotonic()
        self.public_keys, self.private_keys = devnet_keys(n, f, seed)
        self.script = CommitteeScript(
            self.public_keys,
            self.private_keys,
            chain_id=int(cfg["chain_id"]),
            seed=seed,
            txs_per_block=int(cfg["txs_per_block"]),
            eras=eras,
            workers=min(int(script["workers"]), os.cpu_count() or 1),
        )
        self.script.start()
        t1 = time.monotonic()
        bench.traffic.start()
        self.balances = {**bench.traffic.balances(), **self.script.peer_balances()}
        self.net = CommitteeValidator(
            self.public_keys,
            self.private_keys[0],
            self.script,
            chain_id=int(cfg["chain_id"]),
            seed=seed,
            txs_per_block=int(cfg["txs_per_block"]),
            initial_balances=self.balances,
            rbc_batch=bool(cfg["rbc_batch"]),
        )
        bench.say(
            f"committee script: keys and votes {t1 - t0:.1f} s, {eras} eras of "
            f"tables building; validator 0 up after {time.monotonic() - t1:.1f} s more"
        )
        self.load = _LoadAfterTables(
            self.script,
            bench,
            bench.traffic,
            bench.record,
            submit=self._submit,
            backlog_now=lambda: len(self.net.node.pool),
            clock=time.monotonic,
        )

    # -- eras ---------------------------------------------------------------------
    def _era(self, profile: bool = False) -> None:
        if self.era >= self.script.eras:
            self.script.out_of_tables(self.era + 1)  # check() says so
            time.sleep(0.05)
            return
        self.load.hand_over(self.load.due())
        self.era += 1
        if profile:
            self.bench.start_slice()
        t0 = time.monotonic()
        block = self.net.run_era(self.era, max_messages=devnet.MAX_MESSAGES)
        now = time.monotonic()
        self.era_s.append(now - t0)
        if profile:
            self.bench.stop_slice()
        router = self.net.router
        self.slots[self.era] = sorted(router.hb_host(self.era).result or {})
        self.coins[self.era] = router.coin_values(self.era)
        self.bench.record.blocks.append(
            BlockSeen(block.header.index, now, block.hash(), tuple(block.tx_hashes))
        )

    def run_window(self, seconds: float) -> None:
        # set-up built tables for eras of era_floor_s; where the warm eras ran
        # faster (a small committee), the tables the window needs at half the
        # fastest are built here, before the window opens
        floor = float(self.cfg["script"]["era_floor_s"])
        fastest = min(self.era_s, default=floor)
        if fastest < floor:
            self.script.extend(
                self.era
                + math.ceil(seconds / (fastest / 2))
                + int(self.cfg["drain_eras_max"])
            )
        super().run_window(seconds)

    # -- correct --------------------------------------------------------------------
    def check(self) -> list:
        from lachain_tpu.core.types import warm_sender_caches

        cfg = self.cfg
        chain_id, n, f = int(cfg["chain_id"]), int(cfg["n"]), int(cfg["f"])
        ref = reference_share.ShareReference(self.private_keys, f, chain_id)
        wrong = list(self.script.problems)
        bm = self.net.node.block_manager
        known = dict(self.sent)
        chain = []
        for seen in self.bench.record.blocks:
            era = seen.height
            block = bm.block_by_height(era)
            if block is None or block.hash() != seen.block_hash:
                wrong.append(f"height {era}: validator 0 holds another block")
                continue
            if self.slots.get(era) != list(range(n)):
                wrong.append(f"era {era}: the ACS held {len(self.slots.get(era, ()))} slots")
            want = ref.block(era, self.script.ciphertexts(era))
            for stx in want.txs:
                known.setdefault(stx.hash(), stx)
            wrong += ref.compare(era, block, want, self.coins.get(era, {}))
            wrong += reference_share.multisig_failures(
                block, self.public_keys.ecdsa_pub_keys, n - f
            )
            if any(h not in known for h in seen.tx_hashes):
                wrong.append(f"height {era}: a transaction nobody sent")
                continue
            chain.append((block, [known[h] for h in seen.tx_hashes]))
        for block, txs in chain:
            warm_sender_caches(txs, chain_id)
        wrong += reference.reexecute(
            chain_id, self.balances, self.public_keys.ecdsa_pub_keys, chain
        )
        bad, credit, nonces = reference.ledger(chain_id, chain, known)
        wrong += bad
        held = {h for block, _txs in chain for h in block.tx_hashes}
        lost = [h for h in self.bench.record.attempted() if h not in held]
        if lost:
            wrong.append(f"{len(lost)} client transaction(s) in no block")
        node = self.net.node
        for j, (block, _txs) in enumerate(chain):
            last = j == len(chain) - 1
            wrong += reference.read_back(
                node.state,
                bm,
                block,
                credit if last else None,
                nonces if last else None,
            )
        return wrong

    def close(self) -> None:
        if self.script is not None:
            self.script.close()
        if self.net is not None:
            self.net.close()
