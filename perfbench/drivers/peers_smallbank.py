"""Driver `peers_smallbank`: the `peers` driver (N real nodes over localhost
TCP, validator 0 in the benchmark's process, the others its children) serving
Blockbench's Smallbank contract: the mix's transactions are the contract's
deployment and then calls of it (perfbench/traffic_smallbank.py), every one
an ordinary signed transaction through Node.submit_tx at validator 0.

Nothing of `peers` is copied but the spawn line that names the child's module
(as `peers_wan`): its driver and its child loop look `_store_report` up in
their module when they call it, so this module wraps it there, in the
benchmark's process when the driver is imported and in each child, which is
this file run as a module. A report that is asked for Smallbank accounts or
getBalance receipts (entries of `addresses` that are no 20-byte address, see
`_ask`) answers with the contract's storage words and the receipts' return
data instead. check() is the `peers` check plus reference_smallbank's dict
model against ALL N stores. Around its window the driver has
perfbench/counter_ratios.py take the cell's metrics that are ratios of
counters.
"""
from __future__ import annotations

import asyncio
import os
import subprocess
import sys
import time
from typing import Dict, List, Tuple

from perfbench import counter_ratios, reference_smallbank, traffic_smallbank
from perfbench.drivers import peers
from perfbench.spec import ROOT

_plain_store_report = peers._store_report

# what a report's `addresses` may carry besides 20-byte addresses, told apart
# by length: the contract (21 bytes), an account id (33: the string's bytes
# behind zeros, which a decimal id has none of), a transaction hash
_CONTRACT, _ACCOUNT = b"C", b"A"


def _ask(contract: bytes, accounts: List[bytes], tx_hashes: List[bytes]) -> List[bytes]:
    return (
        [_CONTRACT + contract]
        + [_ACCOUNT + a.rjust(32, b"\0") for a in accounts]
        + list(tx_hashes)
    )


def _receipt(node, tx_hash: bytes):
    from lachain_tpu.core.types import TransactionReceipt

    raw = node.block_manager.receipt_by_hash(tx_hash)
    return None if raw is None else TransactionReceipt.decode(raw)


def _smallbank_report(node, height: int, asked: List[bytes]) -> dict:
    """What this validator's store holds as of `height`: [saving, checking]
    of each account asked for, read from the contract's storage, and the
    return data of each receipt asked for (None where there is none)."""
    contract = next(a[1:] for a in asked if len(a) == 21 and a[:1] == _CONTRACT)
    snap = node.state.new_snapshot(node.state.roots_at(height))

    def word(tag: bytes, account: bytes) -> int:
        raw = snap.get(
            "storage", contract + reference_smallbank.storage_key(tag, account)
        )
        return int.from_bytes(raw, "big") if raw else 0

    accounts = [
        a[1:].lstrip(b"\0") for a in asked if len(a) == 33 and a[:1] == _ACCOUNT
    ]
    receipts = [_receipt(node, a) for a in asked if len(a) == 32]
    return {
        "words": [
            [
                word(reference_smallbank.TAG_SAVING, a),
                word(reference_smallbank.TAG_CHECKING, a),
            ]
            for a in accounts
        ],
        "returns": [
            None if r is None else int.from_bytes(r.return_data, "big")
            for r in receipts
        ],
    }


def _store_report(node, height: int, addresses) -> dict:
    if all(len(a) == 20 for a in addresses):
        return _plain_store_report(node, height, addresses)
    return {"smallbank": _smallbank_report(node, height, list(addresses))}


peers._store_report = _store_report


class Driver(peers.Driver):
    def __init__(self, cell, bench):
        super().__init__(cell, bench)
        # the harness made a transfer generator for the mix; its signer child
        # refuses this kind, so the stream comes from the module that knows it
        bench.traffic = traffic_smallbank.SmallbankTraffic(
            cell.traffic,
            cell.seed,
            int(cell.config["chain_id"]),
            int(cell.config["txs_per_block"]),
        )
        self.contract = b""
        self.deploy_hash = b""

    def setup(self) -> None:
        # asked before any child is spawned: a program that lacks the
        # contract fails here, inside set-up, and leaves no process behind
        try:
            from lachain_tpu.vm.contracts import smallbank  # noqa: F401
        except ImportError:
            raise RuntimeError(
                "this program has no lachain_tpu.vm.contracts.smallbank: it "
                "cannot serve the Smallbank contract"
            ) from None
        self.contract = traffic_smallbank.contract_address(
            self.cell.traffic, self.cell.seed
        )
        super().setup()

    def _spawn(self, index: int) -> peers._Child:
        # peers.Driver._spawn with this module as the child's
        stderr_path = os.path.join(self.bench.rundir, f"validator{index}.stderr")
        with open(stderr_path, "wb") as err:
            proc = subprocess.Popen(
                [sys.executable, "-m", "perfbench.drivers.peers_smallbank"],
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

    # -- load -----------------------------------------------------------------------
    def _call_blocks(self) -> int:
        """Committed blocks of calls: those after the deployment's that are
        not empty; -1 while the deployment has not committed."""
        blocks = self.bench.record.blocks
        for i, b in enumerate(blocks):
            if self.deploy_hash in b.tx_hashes:
                return sum(1 for later in blocks[i + 1 :] if later.tx_hashes)
        return -1

    async def _generate(self) -> None:
        """The stream's first transaction deploys the contract; no call is
        handed over before its block has committed (a call of an address
        without code would execute as a transfer of nothing)."""
        self.load.hand_over([time.monotonic()])
        (self.deploy_hash,) = self.sent
        while self._call_blocks() < 0:
            await self._block_event.wait()
            self._block_event.clear()
        await super()._generate()

    def warm(self) -> None:
        super().warm()
        want = int(self.cfg["warm"].get("call_blocks", 1))
        self.loop.run_until_complete(
            self._until(
                lambda: self._call_blocks() >= want,
                120.0,
                f"{want} block(s) of calls after the deployment",
            )
        )

    def run_window(self, seconds: float) -> None:
        from lachain_tpu.utils import metrics

        before = metrics.counters_with_prefix("")
        super().run_window(seconds)
        counter_ratios.note(
            self.bench, self.cell.per_layer, before, metrics.counters_with_prefix("")
        )

    # -- correct --------------------------------------------------------------------
    def _calls(self) -> List[Tuple[bytes, bytes]]:
        """(hash, calldata) of every committed call, in block and in-block
        order, from what the generator sent."""
        return [
            (h, self.sent[h].tx.invocation)
            for seen in self.bench.record.blocks
            for h in seen.tx_hashes
            if h in self.sent and self.sent[h].tx.to == self.contract
        ]

    def check(self) -> list:
        wrong = super().check()
        height = self.node.block_manager.current_height()
        receipt = _receipt(self.node, self.deploy_hash)
        if receipt is None or receipt.status != 1 or receipt.return_data != self.contract:
            wrong.append("the deployment's receipt does not name the contract's address")
        calls = self._calls()
        bank, returns = reference_smallbank.replay(calls)
        accounts = sorted(bank.touched)
        asked = _ask(self.contract, accounts, [h for h, _v in returns])
        for child in self.children:
            child.send(
                {
                    "cmd": "report",
                    "height": height,
                    "timeout": 30,
                    "addresses": [a.hex() for a in asked],
                }
            )
        reports = [_smallbank_report(self.node, height, asked)] + [
            child.recv(120)["smallbank"] for child in self.children
        ]
        want_words, want_returns = bank.balances(accounts), [v for _h, v in returns]
        for v, report in enumerate(reports):
            wrong += reference_smallbank.compare(
                f"validator {v}", want_words, want_returns, report
            )
        by_op: Dict[str, int] = {}
        for _h, calldata in calls:
            name = reference_smallbank.decode(calldata)[0]
            by_op[name] = by_op.get(name, 0) + 1
        self.bench.say(
            f"smallbank: {len(calls)} committed calls {by_op}; {len(accounts)} "
            f"accounts touched and {len(returns)} getBalance receipts compared "
            f"with the dict model in {len(reports)} stores"
        )
        return wrong


if __name__ == "__main__":
    asyncio.run(peers._child())
