"""In-process TCP fleet harness: WAN shaping + zero-downtime rolling upgrades.

Boots N full Nodes (core/node.py) over real loopback TCP — signed batches,
per-peer workers, synchronizer, watchdog — the same stack a container fleet
runs, minus the containers. Three jobs:

  * **WAN emulation**: a `LinkShaper` (network/faults.py) installed on every
    node's TcpFrameFilter stripes the fleet across emulated regions with a
    per-region-pair latency/jitter/bandwidth matrix, seeded so two same-seed
    runs shape identically.
  * **Rolling upgrades**: `roll_node(i)` stops node i, rebuilds it from the
    same keys on the upgraded wire (`network/wire.py` LTRX handshake), and
    waits for it to resync and read healthy before the next roll — the
    `lachain-tpu fleet-upgrade` drill and the upgrade tests drive this.
  * **Deterministic traffic**: `submit_and_settle()` paces open-loop load so
    every live node's pool agrees before an era proposes. With
    txs_per_block >= the paced batch size, every proposer proposes the same
    set, the HB union is that set regardless of which proposer slots decide,
    and committed block content is identical between a drill run and its
    no-upgrade control — the block-hash gate the upgrade test asserts.

The harness is test/CLI infrastructure, not a production entrypoint; real
fleets are composed from configs (DEPLOY.md "WAN operations & rolling
upgrades").
"""
from __future__ import annotations

import asyncio
import logging
import random
import time
from typing import Dict, List, Optional

from ..consensus.keys import trusted_key_gen
from ..network.faults import LinkShaper
from .node import Node
from .types import SignedTransaction

logger = logging.getLogger(__name__)

DEFAULT_CHAIN_ID = 225


class TcpFleet:
    """N validators over loopback TCP, optionally link-shaped, rollable."""

    def __init__(
        self,
        n: int = 6,
        f: int = 1,
        *,
        chain_id: int = DEFAULT_CHAIN_ID,
        seed: int = 0,
        txs_per_block: int = 128,
        initial_balances: Optional[Dict[bytes, int]] = None,
        flush_interval: float = 0.01,
        shaper: Optional[LinkShaper] = None,
        fault_seed: int = 0,
        legacy_wire: bool = False,
        era_timeout: float = 60.0,
    ):
        self.n, self.f = n, f
        self.chain_id = chain_id
        self.txs_per_block = txs_per_block
        self.flush_interval = flush_interval
        self.shaper = shaper
        self.fault_seed = fault_seed
        # legacy_wire=True boots every node WITHOUT the LTRX version
        # handshake (a pre-handshake build): the rolling-upgrade drill
        # starts here and rolls node-by-node onto the advertising wire,
        # making the roll a genuine mixed-version upgrade
        self.legacy_wire = legacy_wire
        self.era_timeout = era_timeout
        self.initial_balances = dict(initial_balances or {})
        rng = random.Random(seed)

        class _Rng:
            def randbelow(self, k):
                return rng.randrange(k)

        self.public_keys, self.private_keys = trusted_key_gen(n, f, rng=_Rng())
        self.nodes: List[Optional[Node]] = [None] * n
        self.upgraded: List[bool] = [False] * n
        # eras each node missed while down (the zero-missed-eras gate is
        # about the FLEET: every era must commit; a rolling node sitting
        # one out is the expected shape, a fleet-wide miss is the failure)
        self.missed_eras: Dict[int, List[int]] = {}

    # -- boot ---------------------------------------------------------------

    def _make_node(self, i: int, *, upgraded: bool) -> Node:
        node = Node(
            index=i,
            public_keys=self.public_keys,
            private_keys=self.private_keys[i],
            chain_id=self.chain_id,
            initial_balances=self.initial_balances,
            txs_per_block=self.txs_per_block,
            flush_interval=self.flush_interval,
        )
        if self.legacy_wire and not upgraded:
            # pre-handshake build: no LTRX advert on outbound batches
            node.network.factory.handshake = False
        return node

    def _install_shaper(self, node: Node, i: int) -> None:
        if self.shaper is None:
            return
        node.network.install_wan_shaper(
            self.shaper, i, self.public_keys.ecdsa_pub_keys, self.fault_seed
        )

    async def start(self, first_era: int = 1) -> None:
        for i in range(self.n):
            node = self._make_node(i, upgraded=False)
            self.nodes[i] = node
            await node.start(first_era)
            self._install_shaper(node, i)
        self._connect_all()

    def _connect_all(self) -> None:
        addrs = [nd.address for nd in self.nodes if nd is not None]
        for nd in self.nodes:
            if nd is not None:
                nd.connect([a for a in addrs if a.public_key != nd.ecdsa_pub])

    def live(self) -> List[Node]:
        return [nd for nd in self.nodes if nd is not None]

    def region_of(self, i: int) -> str:
        return self.shaper.region_of(i) if self.shaper is not None else ""

    # -- paced open-loop traffic -------------------------------------------

    async def submit_and_settle(
        self, txs: List[SignedTransaction], *, timeout: float = 30.0
    ) -> None:
        """Submit `txs` to the first live node and wait until every live
        node's pool holds all of them — the pacing that makes proposals
        (hence committed block content) identical across runs."""
        entry = self.live()[0]
        # in-process pacing, not an acknowledgement: what is waited for
        # below arrives through the entry node's frames, which leave after
        # the pool's barrier
        for stx in txs:
            if not entry.submit_tx(stx):
                raise RuntimeError(f"tx rejected by pool: {stx.hash().hex()}")
        hashes = [stx.hash() for stx in txs]
        deadline = time.monotonic() + timeout
        while True:
            settled = all(
                all(nd.pool.get(h) is not None for h in hashes)
                for nd in self.live()
            )
            if settled:
                return
            if time.monotonic() > deadline:
                raise TimeoutError("tx gossip did not settle fleet-wide")
            await asyncio.sleep(0.02)

    # -- era loop -----------------------------------------------------------

    async def run_era(self, era: int) -> bytes:
        """Run era `era` on every live node; records the miss for any node
        sitting it out (mid-roll). Returns the committed header hash —
        identical on every live node or this raises."""
        live = self.live()
        for i, nd in enumerate(self.nodes):
            if nd is None:
                self.missed_eras.setdefault(i, []).append(era)
        blocks = await asyncio.gather(
            *(nd.run_era(era, timeout=self.era_timeout) for nd in live)
        )
        hashes = {b.header.hash() for b in blocks}
        if len(hashes) != 1:
            raise RuntimeError(f"era {era}: fleet forked ({len(hashes)} heads)")
        return hashes.pop()

    def health_statuses(self) -> Dict[int, str]:
        return {
            i: nd.health()["status"]
            for i, nd in enumerate(self.nodes)
            if nd is not None
        }

    # -- rolling upgrade ----------------------------------------------------

    async def take_down(self, i: int) -> int:
        """Stop node i for its upgrade window; returns its tip height.
        Survivors keep running eras (the caller drives them) — n-f must
        still clear quorum with one node out, which is exactly the
        zero-downtime claim the drill certifies."""
        old = self.nodes[i]
        assert old is not None
        tip = old.block_manager.current_height()
        self.nodes[i] = None
        await old.stop()
        return tip

    async def bring_up(
        self, i: int, *, next_era: int, resync_timeout: float = 60.0
    ) -> Node:
        """Rebuild node i on the upgraded wire (LTRX handshake on),
        reconnect it, and wait until it has resynced to the CURRENT fleet
        tip — including any eras the survivors committed while it was
        down. Fresh store on purpose (the harsher restart): the node must
        resync every block over the upgraded wire, exercising sync interop
        between wire versions, not just consensus interop."""
        assert self.nodes[i] is None, "take_down first"
        node = self._make_node(i, upgraded=True)
        self.upgraded[i] = True
        await node.start(next_era)
        self._install_shaper(node, i)
        self.nodes[i] = node
        self._connect_all()
        target = max(
            nd.block_manager.current_height()
            for nd in self.live()
            if nd is not node
        )
        deadline = time.monotonic() + resync_timeout
        while node.block_manager.current_height() < target:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"node {i} did not resync to height {target} after "
                    "upgrade"
                )
            await asyncio.sleep(0.05)
        return node

    async def stop(self) -> None:
        for nd in self.live():
            await nd.stop()

    # -- observability ------------------------------------------------------

    def rtt_ms(self) -> float:
        """Max observed SRTT across the fleet, in ms (the curve's x axis)."""
        vals = [nd.network.rtt.max_srtt() for nd in self.live()]
        return round(max(vals) * 1000.0, 3) if vals else 0.0

    def wire_versions(self) -> Dict[int, int]:
        return {
            i: nd.network.factory.wire_version
            if nd.network.factory.handshake
            else 1
            for i, nd in enumerate(self.nodes)
            if nd is not None
        }
