"""Driver `peers_roll`: the `peers` driver (N real nodes over localhost TCP,
validator 0 in the benchmark's process, the others its children) with one
of the children always down or coming back. From `roll.first_kill_s` into
the window on, the driver SIGKILLs the victim the configuration names,
reaps it, starts a new process on the same store directory, port and keys,
waits until that process reports that it finished an era by consensus, and
kills the next; it keeps a log of every kill (victim, pids, t_kill,
t_reaped, t_spawned, t_listening, t_rejoined) that
perfbench/reference_roll.py checks against the configuration's words.

The child is this file run as a module. It differs from `peers`' child in
what a restart needs: it takes its port, opens a store that may hold a
chain, checks that store against the chain's hashes before it connects
(guarantee 4) and says so, starts `Node.run(first_era=height + 1)`, and
reports `rejoined` with the seconds of each phase of the node's own
recovery clock (lachain_tpu/core/recovery.py) and of the spans `lsm.open`,
`node.recover.pool`, `node.recover.journal` from its tracer.

check() is the `peers` check (equal hashes, every transaction read back,
from all N stores, the restarted ones included) plus: every reopened store
clean or repaired and on the chain, no evidence of equivocation in any of
the N nodes, no gap between two blocks over `roll.max_block_gap_s`, the
log against reference_roll's schedule, and every committed header's
signatures against the quorum.
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

from perfbench import reference_roll  # noqa: E402
from perfbench.drivers import peers  # noqa: E402
from perfbench.spec import ROOT  # noqa: E402
from perfbench.traffic import Traffic  # noqa: E402

# spans a restarted child reads from its own tracer, with the arguments it
# passes on; all three are complete before the node listens
_OPEN_SPANS = {
    "lsm.open": ("wal_records", "repaired"),
    "node.recover.pool": ("restored",),
    "node.recover.journal": ("rearmed", "eras"),
}


def _make_node(spec: dict, index: int, pub, priv, balances):
    """`peers._make_node` on the port the peers know (0: any)."""
    from lachain_tpu.core.node import Node
    from lachain_tpu.storage.lsm import LsmKV

    kv = LsmKV(os.path.join(spec["stores"], f"validator{index}.db"))
    node = Node(
        index=index,
        public_keys=pub,
        private_keys=priv,
        chain_id=int(spec["chain_id"]),
        kv=kv,
        port=int(spec.get("port", 0)),
        txs_per_block=int(spec["txs_per_block"]),
        initial_balances=balances,
        block_interval=float(spec["block_interval_s"]),
    )
    return node, kv


def _store_check(node, kv, chain: List[str]) -> dict:
    """Guarantee (4), before the node connects: fsck's verdict on the
    reopened store (a fatal one never gets here: Node raises), and every
    block it holds against the chain's hash at that height. `chain[h - 1]`
    is the hash validator 0 committed at height h; a block above what
    validator 0 had when this process was spawned goes back as `beyond`."""
    report = node.fsck_report
    bm = node.block_manager
    tip = bm.current_height()
    bad, beyond = [], []
    for h in range(1, tip + 1):
        block = bm.block_by_height(h)
        if block is None:
            bad.append(h)
        elif h <= len(chain):
            if block.hash().hex() != chain[h - 1]:
                bad.append(h)
        else:
            beyond.append([h, block.hash().hex()])
    return {
        "fsck": "clean" if report.clean else "repaired",
        "repaired": len(report.repaired),
        "issues": [i.code for i in report.issues],
        "tip": tip,
        "bad": bad,
        "beyond": beyond,
        **getattr(kv, "opened_with", {}),
    }


def _open_spans() -> dict:
    from lachain_tpu.utils import tracing

    out = {}
    for s in tracing.snapshot():
        if s["name"] in _OPEN_SPANS and not s["open"]:
            out[s["name"]] = {
                "s": s["end"] - s["start"],
                **{k: s["args"].get(k) for k in _OPEN_SPANS[s["name"]]},
            }
    return out


def _roll_report(node) -> dict:
    return {"evidence": len(node.evidence)}


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
    index, restart = int(spec["index"]), bool(spec.get("restart"))
    pub, privs = peers._keys(int(spec["n"]), int(spec["f"]), int(spec["seed"]))
    traffic = Traffic(
        spec["mix"], int(spec["seed"]), int(spec["chain_id"]), int(spec["txs_per_block"])
    )
    node, kv = _make_node(spec, index, pub, privs[index], traffic.balances())
    if restart:
        if node.recovery is None:
            raise RuntimeError("restarted on a store that holds no chain")
        say({"store": _store_check(node, kv, spec["chain"])})
    height = node.block_manager.current_height()
    await node.start(first_era=height + 1)
    say(
        {
            "port": node.address.port,
            "pub": node.address.public_key.hex(),
            "t_listening": time.monotonic(),
        }
    )
    opened = _open_spans() if restart else {}
    peer_list = json.loads(await lines.get())["peers"]
    node.connect(
        [
            PeerAddress(public_key=bytes.fromhex(p["pub"]), host=p["host"], port=p["port"])
            for p in peer_list
        ]
    )
    say({"connected": True})

    def rejoined() -> None:
        # said from inside run_era, before the loop turns to the next era
        say(
            {
                "rejoined": node.recovery.rejoined_era,
                "recovery": node.recovery.report(),
                "spans": opened,
            }
        )

    run_task: Optional[asyncio.Task] = None
    if restart:
        # the chain moves on: no one tells a restarted validator to run
        node.recovery.on_rejoined = rejoined
        run_task = asyncio.ensure_future(node.run(first_era=height + 1))
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
            addresses = [bytes.fromhex(a) for a in msg["addresses"]]
            say(
                {
                    **peers._store_report(node, height, addresses),
                    "roll": _roll_report(node),
                }
            )
        elif msg["cmd"] == "stop":
            await node.stop()
            kv.close()
            say({"stopped": True})
            return


# -- the driver -------------------------------------------------------------------------


class _Child(peers._Child):
    """A child that remembers the address it announced: a restart gets the
    same port, and is told of the same peers."""

    port: Optional[int] = None
    pub: Optional[str] = None

    def recv(self, timeout: float) -> dict:
        msg = super().recv(timeout)
        if "port" in msg and "pub" in msg:
            self.port, self.pub = int(msg["port"]), msg["pub"]
        return msg

    def ready(self) -> bool:
        return b"\n" in self._buf or bool(
            select.select([self.proc.stdout.fileno()], [], [], 0)[0]
        )


class Driver(peers.Driver):
    def __init__(self, cell, bench):
        super().__init__(cell, bench)
        self.roll = self.cfg["roll"]
        self.log: List[dict] = []
        self.roll_task: Optional[asyncio.Task] = None
        self._spawned = 0

    # -- set-up -------------------------------------------------------------------
    def setup(self) -> None:
        # asked before any child is spawned: a program that cannot time a
        # restart fails here, inside set-up, and leaves no process behind
        try:
            from lachain_tpu.core import recovery  # noqa: F401
        except ImportError:
            raise RuntimeError(
                "this program has no lachain_tpu.core.recovery: a restarted "
                "node cannot say when it is back"
            ) from None
        super().setup()

    def _spawn(self, index: int) -> _Child:
        # peers.Driver._spawn with this module as the child's, and a stderr
        # file of its own for every process a validator has had
        self._spawned += 1
        stderr_path = os.path.join(
            self.bench.rundir, f"validator{index}.{self._spawned}.stderr"
        )
        with open(stderr_path, "wb") as err:
            proc = subprocess.Popen(
                [sys.executable, "-m", "perfbench.drivers.peers_roll"],
                cwd=str(ROOT),
                env=dict(os.environ, **self.roll["restart"]["env"]),
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                stderr=err,
                bufsize=0,
                process_group=self.pgid if self.pgid is not None else 0,
            )
        if self.pgid is None:
            self.pgid = proc.pid
        return _Child(index, proc, stderr_path)

    def _peers_msg(self) -> dict:
        address = self.node.address
        return {
            "peers": [
                {"host": address.host, "port": address.port, "pub": address.public_key.hex()}
            ]
            + [
                {"host": "127.0.0.1", "port": c.port, "pub": c.pub}
                for c in self.children
            ]
        }

    # -- the roll -----------------------------------------------------------------
    async def _recv(self, child: _Child, timeout: float) -> dict:
        """A child's next line without blocking validator 0's loop."""
        deadline = time.monotonic() + timeout
        while not child.ready():
            if child.proc.poll() is not None and not child.ready():
                raise child.fail("exited early")
            if time.monotonic() > deadline:
                raise child.fail(f"did not answer within {timeout:.0f} s")
            await asyncio.sleep(0.005)
        return child.recv(5.0)

    async def _restart(self, victim: int) -> None:
        old = self.children[victim - 1]
        entry = {"victim": victim, "old_pid": old.proc.pid, "t_kill": time.monotonic()}
        self.log.append(entry)
        os.kill(old.proc.pid, signal.SIGKILL)
        old.proc.wait()
        entry["t_reaped"] = time.monotonic()
        old.proc.stdin.close()
        old.proc.stdout.close()
        new = self._spawn(victim)
        new.port, new.pub = old.port, old.pub
        entry["t_spawned"] = time.monotonic()
        entry["new_pid"] = new.proc.pid
        self.children[victim - 1] = new
        timeout = float(self.roll["restart_timeout_s"])
        new.send(
            {
                **self._spec(),
                "index": victim,
                "port": old.port,
                "restart": True,
                "chain": [b.block_hash.hex() for b in self.bench.record.blocks],
            }
        )
        entry["store"] = (await self._recv(new, timeout))["store"]
        up = await self._recv(new, timeout)
        if up["port"] != old.port or up["pub"] != old.pub:
            raise new.fail("came back under another address")
        entry["t_listening"] = up["t_listening"]
        new.send(self._peers_msg())
        await self._recv(new, timeout)
        back = await self._recv(new, timeout)
        entry["rejoined_era"] = back["rejoined"]
        entry["recovery"], entry["spans"] = back["recovery"], back["spans"]
        entry["t_rejoined"] = back["recovery"]["marks"]["rejoin"]
        rec = back["recovery"]
        self.bench.say(
            f"validator {victim} killed {entry['t_kill'] - self.bench.record.window_start:.2f} s "
            f"into the window, back after {entry['t_rejoined'] - entry['t_kill']:.2f} s: "
            f"listening {rec['marks']['listening'] - entry['t_kill']:.2f}, connect "
            f"{rec['connect_s']:.2f}, catch-up {rec['catch_up_s']:.2f} ({rec['blocks']} "
            f"blocks), rejoin {rec['rejoin_s']:.2f} (era {back['rejoined']}, "
            f"{rec['eras_synced']} eras superseded); every peer heard after "
            f"{rec['all_seen_s']} s; store {entry['store']['fsck']} "
            f"{entry['store']['issues']}, {entry['store'].get('wal_records')} WAL "
            f"records replayed, torn tail {entry['store'].get('repaired')} bytes, "
            f"{entry['spans'].get('node.recover.pool', {}).get('restored')} "
            f"pooled transactions restored"
        )

    async def _roll(self) -> None:
        record, roll = self.bench.record, self.roll
        while not record.window_end:  # run_window's own coroutine sets it
            await asyncio.sleep(0)
        lo = int(roll["victims"]["from"])
        # a rehearsal's committee is smaller than the configuration's
        count = min(int(roll["victims"]["to"]) - lo + 1, len(self.children))
        t_first = record.window_start + float(roll["first_kill_s"])
        t_last = record.window_end - float(roll["at_window_end"]["no_kill_in_last_s"])
        if t_first > t_last:
            return  # the window holds no moment at which a kill is allowed
        await asyncio.sleep(max(t_first - time.monotonic(), 0.0))
        k = self.cell.seed % count
        while time.monotonic() <= t_last:
            await self._restart(lo + k % count)
            k += 1
            await asyncio.sleep(float(roll["dwell_s"]))

    def _alive(self) -> None:
        super()._alive()
        task = self.roll_task
        if task is not None and task.done() and not task.cancelled():
            task.result()  # the roll ended: raise what ended it, if anything

    def run_window(self, seconds: float) -> None:
        self.roll_task = self.loop.create_task(self._roll())
        # validator 0's pool 10 s into the window (and, in drain(), at its
        # end): a rate the committee cannot hold shows as a pool that grows
        opens = self.bench.record.blocks[-1].t_commit
        self.loop.call_later(
            max(opens + 10.0 - time.monotonic(), 0.0),
            lambda: self.bench.note("pool_at_10s", len(self.node.pool)),
        )
        super().run_window(seconds)

    def drain(self) -> None:
        """The load goes on until the last victim is back (a validator
        still out recovers here, with the chain as busy as in the window);
        then the `peers` drain."""

        self.bench.note("pool_at_end", len(self.node.pool))

        async def go() -> None:
            deadline = time.monotonic() + float(self.cfg["drain_seconds_max"])
            while not self.roll_task.done():
                self._alive()
                if time.monotonic() > deadline:
                    raise RuntimeError("the last victim did not come back in the drain")
                await asyncio.sleep(0.05)
            self.roll_task.result()

        self.loop.run_until_complete(go())
        super().drain()

    # -- correct --------------------------------------------------------------------
    def _min_restarts(self) -> int:
        """A rehearsal asks for as many restarts as its own `roll` section
        says, and for none if it brings no such section."""
        rehearsal = self.cell.rehearsal
        if rehearsal is not None and "roll" not in rehearsal.config:
            return 0
        return int(self.roll["min_restarts"])

    def check(self) -> list:
        wrong = super().check()
        record, roll = self.bench.record, self.roll
        n, f = int(self.cfg["n"]), int(self.cfg["f"])
        # (5) from every process as it stands after the drain
        for child in self.children:
            child.send({"cmd": "report", "height": 0, "timeout": 1, "addresses": []})
        reports = [_roll_report(self.node)] + [
            child.recv(60)["roll"] for child in self.children
        ]
        for v, report in enumerate(reports):
            if report["evidence"]:
                wrong.append(
                    f"validator {v} holds {report['evidence']} record(s) of "
                    f"evidence: somebody equivocated"
                )
        # (4) every reopened store, as its child found it before it connected
        by_height = {b.height: b.block_hash.hex() for b in record.blocks}
        for k, e in enumerate(self.log):
            store = e.get("store")
            if store is None:
                wrong.append(f"restart {k}: validator {e['victim']} never reported its store")
                continue
            if store["bad"]:
                wrong.append(
                    f"restart {k}: validator {e['victim']}'s reopened store is off "
                    f"the chain at heights {store['bad'][:5]}"
                )
            for height, block_hash in store["beyond"]:
                if by_height.get(height) != block_hash:
                    wrong.append(
                        f"restart {k}: validator {e['victim']}'s reopened store "
                        f"held a block {height} the chain never had"
                    )
        # (6) service never stops, at validator 0, from the window's start on
        times = [record.window_start] + [
            b.t_commit for b in record.blocks if b.t_commit > record.window_start
        ]
        gap = max((b - a for a, b in zip(times, times[1:])), default=0.0)
        if gap > float(roll["max_block_gap_s"]):
            wrong.append(
                f"{gap:.2f} s between two blocks at validator 0; the limit is "
                f"{roll['max_block_gap_s']} s"
            )
        # (7) and the schedule, against the configuration's words alone
        scaled = dict(roll)
        if self.cell.rehearsal is not None:
            scaled["victims"] = dict(
                roll["victims"], to=min(int(roll["victims"]["to"]), n - 1)
            )
        ref = reference_roll.RollReference(scaled, n, self.cell.seed)
        wrong += reference_roll.check_schedule(
            ref, self.log, record.window_start, record.window_end,
            min_restarts=self._min_restarts(),
        )
        # the quorum of every committed header, by plain signature checks
        headers = []
        for seen in record.blocks:
            block = self.node.block_manager.block_by_height(seen.height)
            if block is not None:
                headers.append(
                    (seen.height, block.header.hash(), block.multisig.signatures)
                )
        wrong += reference_roll.check_multisigs(n, f, self.pub.ecdsa_pub_keys, headers)
        self._note(gap)
        return wrong

    def _note(self, gap: float) -> None:
        """The layer `recovery`'s readings: one value a completed restart,
        the reductions take their medians."""
        bench, record = self.bench, self.bench.record
        done = [e for e in self.log if e.get("t_rejoined") is not None]
        bench.note(
            "restarts_in_window",
            reference_roll.completed_in_window(self.log, record.window_end),
        )
        bench.note("block_gap_max_s", gap)
        for e in done:
            rec, spans = e["recovery"], e["spans"]
            opened = spans.get("lsm.open", {}).get("s", 0.0)
            journal = spans.get("node.recover.journal", {}).get("s", 0.0)
            bench.note("restart_rejoin_s", e["t_rejoined"] - e["t_kill"])
            # the kill to the listening socket, less the two steps inside
            # it that have metrics of their own
            bench.note(
                "recover_spawn_s",
                rec["marks"]["listening"] - e["t_kill"] - opened - journal,
            )
            bench.note("recover_store_open_s", opened)
            bench.note("recover_journal_s", journal)
            bench.note("recover_connect_s", rec["connect_s"])
            bench.note("recover_catch_up_s", rec["catch_up_s"])
            bench.note("recover_rejoin_s", rec["rejoin_s"])
            bench.note("sync_blocks_per_restart", rec["blocks"])
        if done:
            bench.say(
                f"roll: {len(self.log)} kill(s), {len(done)} back, "
                f"{bench.values['restarts_in_window'][-1]:.0f} inside the window; "
                f"kill to rejoined {[round(e['t_rejoined'] - e['t_kill'], 2) for e in done]} s; "
                f"largest gap between blocks {gap:.2f} s; validator 0's pool "
                f"{bench.values.get('pool_at_10s')} 10 s in, "
                f"{bench.values.get('pool_at_end')} at the window's end"
            )

    # -- always -----------------------------------------------------------------------
    def close(self) -> None:
        if self.roll_task is not None and self.loop is not None:
            self.roll_task.cancel()
            self.loop.run_until_complete(
                asyncio.gather(self.roll_task, return_exceptions=True)
            )
            self.roll_task = None
        super().close()


if __name__ == "__main__":
    asyncio.run(_child())
