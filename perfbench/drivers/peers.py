"""Driver `peers`: N real core/node.Node processes over localhost TCP, each in
the autonomous loop Node.run(). Validator 0 lives in the benchmark's process
(it holds the chip and takes the load through Node.submit_tx, what
eth_sendRawTransaction calls); validators 1..N-1 are children of this module
on the native host backend, which never imports jax. Keys are rebuilt in
each process from the seed. A block is committed, from the client's side,
when validator 0 has persisted it.

Children talk JSON lines over their stdin/stdout. They die with the parent:
all share one process group that close() kills, and each exits when its
stdin reaches end of file. A child that exits early, or does not answer in
time, fails the run with the end of its stderr shown.

Run as a module (`python -m perfbench.drivers.peers`) this file is the child.
"""
from __future__ import annotations

import asyncio
import json
import os
import select
import signal
import subprocess
import sys
import time
from typing import List, Optional

if __name__ == "__main__":
    sys.path.insert(
        0,
        os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ),
    )

from perfbench import reference  # noqa: E402
from perfbench.spec import ROOT  # noqa: E402
from perfbench.traffic import BlockSeen, Load, SeededRng, Traffic  # noqa: E402


def _keys(n: int, f: int, seed: int):
    from lachain_tpu.consensus.keys import trusted_key_gen

    return trusted_key_gen(n, f, rng=SeededRng(seed))


def _make_node(spec: dict, index: int, pub, priv, balances):
    """The node a default config gives (cli._build_node): LsmKV under the
    run's directory, every other argument the program's default."""
    from lachain_tpu.core.node import Node
    from lachain_tpu.storage.lsm import LsmKV

    kv = LsmKV(os.path.join(spec["stores"], f"validator{index}.db"))
    node = Node(
        index=index,
        public_keys=pub,
        private_keys=priv,
        chain_id=int(spec["chain_id"]),
        kv=kv,
        txs_per_block=int(spec["txs_per_block"]),
        initial_balances=balances,
        block_interval=float(spec["block_interval_s"]),
    )
    return node, kv


def _store_report(node, height: int, addresses: List[bytes]) -> dict:
    """What this validator's store holds up to `height`: per block its hash
    and how many of its transactions and receipts read back; balances and
    nonces as of `height`."""
    from lachain_tpu.core.execution import get_balance, get_nonce

    bm = node.block_manager
    blocks = []
    for h in range(1, height + 1):
        block = bm.block_by_height(h)
        if block is None:
            blocks.append(None)
            continue
        blocks.append(
            [
                block.hash().hex(),
                len(block.tx_hashes),
                sum(bm.transaction_by_hash(t) is not None for t in block.tx_hashes),
                sum(bm.receipt_by_hash(t) is not None for t in block.tx_hashes),
            ]
        )
    snap = node.state.new_snapshot(node.state.roots_at(height))
    return {
        "blocks": blocks,
        "balances": [get_balance(snap, a) for a in addresses],
        "nonces": [get_nonce(snap, a) for a in addresses],
    }


# -- the child ----------------------------------------------------------------------


async def _child() -> None:
    from lachain_tpu.network.hub import PeerAddress

    loop = asyncio.get_running_loop()
    lines: asyncio.Queue = asyncio.Queue()

    def read_stdin() -> None:
        for line in sys.stdin:
            loop.call_soon_threadsafe(lines.put_nowait, line)
        os._exit(1)  # the parent is gone

    import threading

    threading.Thread(target=read_stdin, daemon=True).start()

    def say(obj: dict) -> None:
        print(json.dumps(obj), flush=True)

    spec = json.loads(await lines.get())
    index = int(spec["index"])
    pub, privs = _keys(int(spec["n"]), int(spec["f"]), int(spec["seed"]))
    traffic = Traffic(
        spec["mix"], int(spec["seed"]), int(spec["chain_id"]), int(spec["txs_per_block"])
    )
    node, kv = _make_node(spec, index, pub, privs[index], traffic.balances())
    await node.start()
    say({"port": node.address.port, "pub": node.address.public_key.hex()})
    peers = json.loads(await lines.get())["peers"]
    node.connect(
        [
            PeerAddress(public_key=bytes.fromhex(p["pub"]), host=p["host"], port=p["port"])
            for p in peers
        ]
    )
    say({"connected": True})
    run_task: Optional[asyncio.Task] = None
    while True:
        getter = asyncio.ensure_future(lines.get())
        waits = [getter] + ([run_task] if run_task is not None else [])
        await asyncio.wait(waits, return_when=asyncio.FIRST_COMPLETED)
        if run_task is not None and run_task.done() and not run_task.cancelled():
            run_task.result()  # the era loop ended: raise what ended it
            raise RuntimeError("Node.run() returned")
        msg = json.loads(await getter)
        if msg["cmd"] == "run":
            run_task = asyncio.ensure_future(node.run(first_era=1))
        elif msg["cmd"] == "report":
            height = int(msg["height"])
            deadline = time.monotonic() + msg["timeout"]
            while node.block_manager.current_height() < height:
                if time.monotonic() > deadline:
                    raise RuntimeError(f"height {height} not reached")
                await asyncio.sleep(0.02)
            if run_task is not None:
                run_task.cancel()
                await asyncio.gather(run_task, return_exceptions=True)
                run_task = None
            say(
                _store_report(
                    node, height, [bytes.fromhex(a) for a in msg["addresses"]]
                )
            )
        elif msg["cmd"] == "stop":
            await node.stop()
            kv.close()
            say({"stopped": True})
            return


# -- the parent's handle on a child -----------------------------------------------------


class _Child:
    def __init__(self, index: int, proc: subprocess.Popen, stderr_path: str):
        self.index, self.proc, self.stderr_path = index, proc, stderr_path
        self._buf = b""

    def fail(self, what: str) -> RuntimeError:
        try:
            with open(self.stderr_path, "rb") as fh:
                tail = fh.read()[-4000:].decode(errors="replace")
        except OSError:
            tail = "(no stderr)"
        return RuntimeError(
            f"validator {self.index} {what} (exit code {self.proc.poll()}); "
            f"its stderr ends:\n{tail}"
        )

    def send(self, obj: dict) -> None:
        try:
            self.proc.stdin.write(json.dumps(obj).encode() + b"\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, OSError):
            raise self.fail("is gone") from None

    def recv(self, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        fd = self.proc.stdout.fileno()
        while b"\n" not in self._buf:
            left = deadline - time.monotonic()
            if left <= 0 or not select.select([fd], [], [], left)[0]:
                raise self.fail(f"did not answer within {timeout:.0f} s")
            chunk = os.read(fd, 1 << 16)
            if not chunk:
                raise self.fail("exited early")
            self._buf += chunk
        line, self._buf = self._buf.split(b"\n", 1)
        return json.loads(line)


# -- the driver -------------------------------------------------------------------------


class Driver:
    def __init__(self, cell, bench):
        self.cell, self.bench = cell, bench
        self.cfg = cell.config
        self.children: List[_Child] = []
        self.pgid: Optional[int] = None
        self.node = None
        self.kv = None
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.run_task: Optional[asyncio.Task] = None
        self.gen_task: Optional[asyncio.Task] = None
        self.sent = {}

    # -- set-up -------------------------------------------------------------------
    def _spec(self) -> dict:
        cfg = self.cfg
        return {
            "n": cfg["n"],
            "f": cfg["f"],
            "seed": self.cell.seed,
            "chain_id": cfg["chain_id"],
            "txs_per_block": cfg["txs_per_block"],
            "block_interval_s": cfg["block_interval_s"],
            "mix": self.cell.traffic,
            "stores": os.path.join(self.bench.rundir, "stores"),
        }

    def _spawn(self, index: int) -> _Child:
        stderr_path = os.path.join(self.bench.rundir, f"validator{index}.stderr")
        with open(stderr_path, "wb") as err:
            proc = subprocess.Popen(
                [sys.executable, "-m", "perfbench.drivers.peers"],
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
        return _Child(index, proc, stderr_path)

    def setup(self) -> None:
        cfg, bench = self.cfg, self.bench
        n, f = int(cfg["n"]), int(cfg["f"])
        spec = self._spec()
        os.makedirs(spec["stores"])
        bench.traffic.start()
        for i in range(1, n):
            child = self._spawn(i)
            self.children.append(child)
            child.send({**spec, "index": i})
        # validator 0, in this process, while the children come up
        pub, privs = _keys(n, f, self.cell.seed)
        self.pub = pub
        self.balances = bench.traffic.balances()
        self.loop = asyncio.new_event_loop()
        self.node, self.kv = _make_node(spec, 0, pub, privs[0], self.balances)
        self.node.block_manager.on_block_persisted.append(self._on_block)
        self.loop.run_until_complete(self.node.start())
        timeout = float(cfg["child_start_timeout_s"])
        peers = [
            {
                "host": self.node.address.host,
                "port": self.node.address.port,
                "pub": self.node.address.public_key.hex(),
            }
        ]
        for child in self.children:
            up = child.recv(timeout)
            peers.append({"host": "127.0.0.1", "port": up["port"], "pub": up["pub"]})
        from lachain_tpu.network.hub import PeerAddress

        for child in self.children:
            child.send({"peers": peers})

        async def connect() -> None:  # peer workers are tasks of the loop
            self.node.connect(
                [
                    PeerAddress(
                        public_key=bytes.fromhex(p["pub"]), host=p["host"], port=p["port"]
                    )
                    for p in peers
                ]
            )

        self.loop.run_until_complete(connect())
        for child in self.children:
            child.recv(timeout)
        self.load = Load(
            bench.traffic,
            bench.record,
            submit=self._submit,
            backlog_now=lambda: len(self.node.pool),
            clock=time.monotonic,
        )

    def _submit(self, stx) -> bool:
        self.sent[stx.hash()] = stx
        return self.node.submit_tx(stx)

    def _on_block(self, block) -> None:
        self.bench.record.blocks.append(
            BlockSeen(
                block.header.index, time.monotonic(), block.hash(), tuple(block.tx_hashes)
            )
        )
        self._block_event.set()

    # -- load -----------------------------------------------------------------------
    async def _generate(self) -> None:
        """Closed loop: top the pool up whenever a block has committed. Open
        loop: hand each transaction over when it is due. Bursts yield to the
        loop between them, as requests arriving one by one would."""
        load, traffic = self.load, self.bench.traffic
        while not load.stopped:
            due = load.due()
            for i in range(0, len(due), traffic.burst):
                load.hand_over(due[i : i + traffic.burst])
                await asyncio.sleep(0)
            if traffic.loop == "closed":
                await self._block_event.wait()
                self._block_event.clear()
            else:
                await asyncio.sleep(
                    max(traffic.next_arrival() - time.monotonic(), 0.0)
                )

    def _alive(self) -> None:
        for child in self.children:
            if child.proc.poll() is not None:
                raise child.fail("exited early")
        for task in (self.run_task, self.gen_task):
            if task is not None and task.done() and not task.cancelled():
                task.result()
                raise RuntimeError("a task of validator 0 ended inside the run")

    async def _until(self, done, timeout: float, what: str) -> None:
        deadline = time.monotonic() + timeout
        while not done():
            self._alive()
            if time.monotonic() > deadline:
                raise RuntimeError(f"{what}: not within {timeout:.0f} s")
            await asyncio.sleep(0.05)

    def warm(self) -> None:
        """Nothing of the device is warmed: the program routes this size to
        the host. The node's own start-up warm-up is waited for, then the
        unmeasured eras run under the cell's load."""

        async def go() -> None:
            self._block_event = asyncio.Event()
            thread = getattr(self.node, "_warmup_thread", None)
            if thread is not None:
                await asyncio.get_running_loop().run_in_executor(None, thread.join)
            for child in self.children:
                child.send({"cmd": "run"})
            self.run_task = asyncio.ensure_future(self.node.run(first_era=1))
            self.load.start()
            self.gen_task = asyncio.ensure_future(self._generate())
            heights = int(self.cfg["warm"]["heights"])
            await self._until(
                lambda: len(self.bench.record.blocks) >= heights,
                120.0,
                f"the first {heights} blocks",
            )

        self.loop.run_until_complete(go())

    def run_window(self, seconds: float) -> None:
        record, bench = self.bench.record, self.bench
        profile = self.cfg["profile"]

        async def go() -> None:
            # the window opens at the commit that ended the warm phase
            record.window_start = record.blocks[-1].t_commit
            record.window_end = record.window_start + seconds
            slice_at = record.window_start + float(profile["skip_seconds"])
            slice_end = slice_at + float(profile["seconds"])
            while (now := time.monotonic()) < record.window_end:
                self._alive()
                if self.cell.trace:
                    if not bench.slicing and not record.slices and now >= slice_at:
                        bench.start_slice()
                    elif bench.slicing and now >= slice_end:
                        bench.stop_slice()
                await asyncio.sleep(min(0.1, record.window_end - now))
            if bench.slicing:
                bench.stop_slice()

        self.loop.run_until_complete(go())

    def drain(self) -> None:
        """No new load; validator 0 goes on until what was attempted is in a
        block, then leaves its era loop."""

        async def go() -> None:
            self.load.stopped = True
            self._block_event.set()
            await asyncio.gather(self.gen_task, return_exceptions=False)
            self.gen_task = None
            deadline = time.monotonic() + float(self.cfg["drain_seconds_max"])
            while self.bench.record.outstanding() and time.monotonic() < deadline:
                self._alive()
                await asyncio.sleep(0.05)
            self.run_task.cancel()
            await asyncio.gather(self.run_task, return_exceptions=True)
            self.run_task = None

        self.loop.run_until_complete(go())

    # -- correct --------------------------------------------------------------------
    def check(self) -> list:
        chain_id = int(self.cfg["chain_id"])
        record = self.bench.record
        height = self.node.block_manager.current_height()
        wrong = []
        if height != record.blocks[-1].height:
            wrong.append("validator 0's height is not its last persisted block's")
        chain = []
        for seen in record.blocks:
            block = self.node.block_manager.block_by_height(seen.height)
            if block is None or block.hash() != seen.block_hash:
                wrong.append(f"height {seen.height}: not read back from validator 0")
                continue
            if any(h not in self.sent for h in seen.tx_hashes):
                wrong.append(f"height {seen.height}: a transaction nobody sent")
                continue
            chain.append((block, [self.sent[h] for h in seen.tx_hashes]))
        wrong += reference.reexecute(
            chain_id, self.balances, self.pub.ecdsa_pub_keys, chain
        )
        bad, credit, nonces = reference.ledger(chain_id, chain, self.sent)
        wrong += bad
        addresses = list(credit) + list(nonces)
        want_state = list(credit.values()), list(nonces.values())
        reports = [_store_report(self.node, height, addresses)]
        for child in self.children:
            child.send(
                {
                    "cmd": "report",
                    "height": height,
                    "timeout": 30,
                    "addresses": [a.hex() for a in addresses],
                }
            )
        for child in self.children:
            reports.append(child.recv(60))
        want_blocks = [
            [b.block_hash.hex(), len(b.tx_hashes), len(b.tx_hashes), len(b.tx_hashes)]
            for b in record.blocks
        ]
        for v, report in enumerate(reports):
            if report["blocks"] != want_blocks:
                wrong.append(
                    f"validator {v}: block hashes, or the transactions and "
                    f"receipts read back, differ from what validator 0 committed"
                )
            got = (
                report["balances"][: len(credit)],
                report["nonces"][len(credit) :],
            )
            if got != want_state:
                wrong.append(f"validator {v}: balances or nonces differ from the ledger")
        return wrong

    # -- always -----------------------------------------------------------------------
    def close(self) -> None:
        try:
            if self.loop is not None and self.node is not None:

                async def stop() -> None:
                    for task in (self.gen_task, self.run_task):
                        if task is not None:
                            task.cancel()
                            await asyncio.gather(task, return_exceptions=True)
                    await self.node.stop()

                self.loop.run_until_complete(stop())
                self.kv.close()
                self.loop.close()
        finally:
            for child in self.children:
                if child.proc.poll() is None:
                    try:
                        child.send({"cmd": "stop"})
                    except RuntimeError:
                        pass
            deadline = time.monotonic() + 10
            for child in self.children:
                try:
                    child.proc.wait(max(deadline - time.monotonic(), 0.1))
                except subprocess.TimeoutExpired:
                    pass
            if self.pgid is not None:
                try:
                    os.killpg(self.pgid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            for child in self.children:
                child.proc.wait()
                child.proc.stdin.close()
                child.proc.stdout.close()


if __name__ == "__main__":
    asyncio.run(_child())
