"""Driver `devnet`: every validator of the committee in the benchmark's own
process, over the program's simulated network (core/devnet.Devnet). Device
work is one validator's (identical batches are deduplicated across
validators); host work is all N validators'. run_era() blocks the thread, so
load is handed over between eras, each transaction keeping the time it was
due. A block is committed, from the client's side, when run_era() returns:
all N validators hold it.
"""
from __future__ import annotations

import random
import time

from .. import reference
from ..traffic import BlockSeen, Load

# the engine's livelock cap counts messages over the network's whole life
# (about 2.5 million an era at N=64), so no cap fits a window; a livelock
# ends at the run's alarm instead
MAX_MESSAGES = 1 << 62


class Driver:
    def __init__(self, cell, bench):
        self.cell, self.bench = cell, bench
        self.cfg = cell.config
        self.net = None
        self.sent = {}
        self.era = 0

    # -- set-up -------------------------------------------------------------------
    def setup(self) -> None:
        from lachain_tpu.core.devnet import Devnet

        cfg, bench = self.cfg, self.bench
        bench.traffic.start()
        self.balances = bench.traffic.balances()
        self.net = Devnet(
            int(cfg["n"]),
            int(cfg["f"]),
            chain_id=int(cfg["chain_id"]),
            seed=self.cell.seed,
            txs_per_block=int(cfg["txs_per_block"]),
            initial_balances=self.balances,
            engine=cfg["engine"],
            rbc_batch=bool(cfg["rbc_batch"]),
        )
        self.load = Load(
            bench.traffic,
            bench.record,
            submit=self._submit,
            backlog_now=lambda: len(self.net.nodes[0].pool),
            clock=time.monotonic,
        )

    def _submit(self, stx) -> bool:
        self.sent[stx.hash()] = stx
        return self.net.submit_tx(stx)

    def warm(self) -> None:
        """The shapes this configuration's cells reach (the configuration's
        `warm` lists them; the zero-compile check proves the list whole),
        then the unmeasured eras under the cell's own load."""
        from lachain_tpu.crypto import bls12381 as bls
        from lachain_tpu.crypto.warmup import warmup_era_kernels
        from lachain_tpu.ops import rs_batch

        cfg, bench = self.cfg, self.bench
        warm = cfg["warm"]
        n, f = int(cfg["n"]), int(cfg["f"])
        t0 = time.monotonic()
        if warm["era_shapes"]:
            warmup_era_kernels(
                n, backend=bench.proxy, shapes=warm["era_shapes"], include_ts=False
            ).join()
        t1 = time.monotonic()
        for points in warm["g2_msm_points"]:
            bench.proxy.g2_msm([bls.G2_GEN] * points, [1] * points)
        t2 = time.monotonic()
        k = n - 2 * f
        rnd = random.Random(self.cell.seed)
        for payload in warm["rs_payload_bytes"]:
            items = [(rnd.randbytes(payload), k, n) for _ in range(n)]
            enc = rs_batch.encode_batch(items)
            rs_batch.decode_batch(
                [([None] * f + sh[f : f + k] + [None] * (n - f - k), k) for sh in enc]
            )
        bench.say(
            f"kernel shapes warm: era batch {t1 - t0:.1f} s, G2 MSM {t2 - t1:.1f} s, "
            f"RS {time.monotonic() - t2:.1f} s"
        )
        self.load.start()
        for _ in range(int(cfg["warm_eras"])):
            self._era()

    # -- eras ---------------------------------------------------------------------
    def _era(self, profile: bool = False) -> None:
        self.load.hand_over(self.load.due())
        self.era += 1
        if profile:
            self.bench.start_slice()
        block = self.net.run_era(self.era, max_messages=MAX_MESSAGES)[0]
        now = time.monotonic()
        if profile:
            self.bench.stop_slice()
        self.bench.record.blocks.append(
            BlockSeen(block.header.index, now, block.hash(), tuple(block.tx_hashes))
        )

    def run_window(self, seconds: float) -> None:
        record = self.bench.record
        record.window_start = time.monotonic()
        record.window_end = record.window_start + seconds
        profile_at = int(self.cfg["profile"]["skip_eras"])
        i = 0
        while time.monotonic() < record.window_end:
            self._era(profile=self.cell.trace and i == profile_at)
            i += 1

    def drain(self) -> None:
        """No new load; eras go on until what was attempted is in a block."""
        self.load.stopped = True
        for _ in range(int(self.cfg["drain_eras_max"])):
            if not self.bench.record.outstanding():
                return
            self._era()

    # -- correct --------------------------------------------------------------------
    def check(self) -> list:
        cfg = self.cfg
        chain_id = int(cfg["chain_id"])
        wrong = []
        chain = []
        for seen in self.bench.record.blocks:
            blocks = [
                node.block_manager.block_by_height(seen.height)
                for node in self.net.nodes
            ]
            if any(b is None or b.hash() != seen.block_hash for b in blocks):
                wrong.append(f"height {seen.height}: validators hold different blocks")
                continue
            missing = [h for h in seen.tx_hashes if h not in self.sent]
            if missing:
                wrong.append(f"height {seen.height}: a transaction nobody sent")
                continue
            chain.append((blocks[0], [self.sent[h] for h in seen.tx_hashes]))
        wrong += reference.reexecute(
            chain_id,
            self.balances,
            self.net.public_keys.ecdsa_pub_keys,
            chain,
        )
        bad, credit, nonces = reference.ledger(chain_id, chain, self.sent)
        wrong += bad
        for node in self.net.nodes:
            for j, (block, _txs) in enumerate(chain):
                last = j == len(chain) - 1
                wrong += reference.read_back(
                    node.state,
                    node.block_manager,
                    block,
                    credit if last else None,
                    nonces if last else None,
                )
        return wrong

    def close(self) -> None:
        if self.net is None:
            return
        self.net.close()
        # Devnet.close() leaves the native engine to __del__, which drains
        # the tracer under its lock: run by the collector inside
        # tracing.snapshot(), that deadlocks. Close it here, while nothing
        # holds the lock (the second close is a no-op).
        close_engine = getattr(self.net.net, "close", None)
        if close_engine is not None:
            close_engine()
