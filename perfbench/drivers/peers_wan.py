"""Driver `peers_wan`: the `peers` driver (N real nodes over localhost TCP,
validator 0 in the benchmark's process, the others its children) with N from
the configuration and the configuration's link shaper in every process.

Nothing of `peers` is copied but the spawn line that names the child's
module: its driver and its child loop look `_make_node` and `_store_report`
up in their module when they call them, so this module wraps the two there —
in the benchmark's process when the driver is imported, and in each child,
which is this file run as a module. A node gets the shaper right after it is
built and before it starts, through the program's one install function
(what cli._build_node calls for network.wanShaper), seeded with the chain
id; a store report carries the process's shaped-frame count and round trips
with it. check() is the `peers` check plus reference_wan's comparison of all
N processes' links with the stated delays.
"""
from __future__ import annotations

import asyncio
import os
import statistics
import subprocess
import sys

from lachain_tpu.network.manager import NetworkManager
from perfbench import reference_wan
from perfbench.drivers import peers
from perfbench.spec import ROOT

_plain_make_node, _plain_store_report = peers._make_node, peers._store_report


def _make_node(spec: dict, index: int, pub, priv, balances):
    node, kv = _plain_make_node(spec, index, pub, priv, balances)
    if "wan" in spec:
        node.network.install_wan_shaper(
            spec["wan"], index, pub.ecdsa_pub_keys, int(spec["chain_id"])
        )
    return node, kv


def _wan_report(node) -> dict:
    """What this process did to its links: frames its fault session shaped,
    and per peer (by validator index) the ping round trips its RttTracker
    saw and their smoothed seconds."""
    filt = node.network.hub.frame_filter
    rtt = node.network.rtt
    seen = rtt.snapshot()
    return {
        "shaped": 0 if filt is None else filt.session.stats["shaped"],
        "rtt": [
            [j, seen.get(pub[:4].hex(), {}).get("samples", 0), rtt.srtt(pub)]
            for j, pub in enumerate(node.public_keys.ecdsa_pub_keys)
            if j != node.index
        ],
    }


def _store_report(node, height: int, addresses) -> dict:
    return {**_plain_store_report(node, height, addresses), "wan": _wan_report(node)}


peers._make_node, peers._store_report = _make_node, _store_report


class Driver(peers.Driver):
    def setup(self) -> None:
        # asked before any child is spawned: a program that lacks the install
        # function fails here, inside set-up, and leaves no process behind
        if not hasattr(NetworkManager, "install_wan_shaper"):
            raise RuntimeError(
                "this program has no NetworkManager.install_wan_shaper: it cannot "
                "stand up a shaped deployment"
            )
        super().setup()

    def _spec(self) -> dict:
        return {**super()._spec(), "wan": self.cfg["network"]["wan"]}

    def _spawn(self, index: int) -> peers._Child:
        # peers.Driver._spawn with this module as the child's
        stderr_path = os.path.join(self.bench.rundir, f"validator{index}.stderr")
        with open(stderr_path, "wb") as err:
            proc = subprocess.Popen(
                [sys.executable, "-m", "perfbench.drivers.peers_wan"],
                cwd=str(ROOT),
                env=dict(os.environ, LACHAIN_TPU_BACKEND="native"),
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                stderr=err,
                bufsize=0,
                process_group=self.pgid if self.pgid is not None else 0,
            )
        if self.pgid is None:
            self.pgid = proc.pid
        return peers._Child(index, proc, stderr_path)

    def check(self) -> list:
        wrong = super().check()
        # a second report, of no block and no account: each child answers
        # with its links as they stand after the drain
        for child in self.children:
            child.send({"cmd": "report", "height": 0, "timeout": 1, "addresses": []})
        reports = [_wan_report(self.node)] + [
            child.recv(60)["wan"] for child in self.children
        ]
        ref = reference_wan.WanReference(self.cfg["network"])
        links = reference_wan.check_links(ref, reports)
        over = [
            srtt / ref.round_trip_floor(i, j)
            for i, r in enumerate(reports)
            for j, _samples, srtt in r["rtt"]
            if srtt is not None
        ]
        self.bench.say(
            f"links: {len(reports)} processes, {len(over)} smoothed round trips "
            f"compared with reference_wan, {len(links)} finding(s); round trip over "
            f"its floor: {min(over, default=0):.2f}x at least, "
            f"{statistics.median(over) if over else 0:.2f}x in the median; "
            f"frames shaped by process: {[r['shaped'] for r in reports]}"
        )
        return wrong + links


if __name__ == "__main__":
    asyncio.run(peers._child())
