"""The one general traffic generator. A mix is a data file of parameters
(perfbench/traffic/<mix>.json); everything drawn here comes from --seed.

Transactions are signed by a child process (perfbench/signer.py) and reach
the benchmark as raw bytes, which it decodes afresh — what
eth_sendRawTransaction does — so the system under test pays signature
recovery as it would for a client, and signing costs the measured process
nothing. The stream is fixed by the seed: transaction k is sent by account
k mod A with nonce k div A.
"""
from __future__ import annotations

import json
import os
import random
import struct
import subprocess
import sys
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from .spec import ROOT

_FRAME = struct.Struct(">H")


class SeededRng:
    """The rng shape the program's key generators take."""

    def __init__(self, seed: int):
        self._r = random.Random(seed)

    def randbelow(self, n: int) -> int:
        return self._r.randrange(n)


def account_keys(seed: int, count: int) -> List[bytes]:
    from lachain_tpu.crypto import ecdsa

    return [
        ecdsa.generate_private_key(SeededRng(seed * 1_000_003 + 17 + i))
        for i in range(count)
    ]


def recipient(seed: int, j: int) -> bytes:
    from lachain_tpu.crypto.hashes import keccak256

    return keccak256(b"perfbench-recipient" + struct.pack(">QQ", seed, j))[:20]


def signed_stream(mix: dict, seed: int, chain_id: int) -> Iterator[bytes]:
    """Raw signed transactions, endlessly, as the mix's `tx` section says."""
    from lachain_tpu.core.types import Transaction, sign_transaction

    tx = mix["tx"]
    if tx["kind"] != "transfer":
        raise ValueError(f"traffic: unknown transaction kind {tx['kind']!r}")
    keys = account_keys(seed, int(mix["accounts"]))
    to = [recipient(seed, j) for j in range(int(tx["recipients"]))]
    cycle = int(tx["gas_price_cycle"])
    k = 0
    while True:
        u = k % len(keys)
        yield sign_transaction(
            Transaction(
                to=to[k % len(to)],
                value=int(tx["value"]),
                nonce=k // len(keys),
                gas_price=1 + (k % cycle),
                gas_limit=int(tx["gas_limit"]),
            ),
            keys[u],
            chain_id,
        ).encode()
        k += 1


@dataclass
class BlockSeen:
    height: int
    t_commit: float  # time.monotonic() when the client side learnt of it
    block_hash: bytes
    tx_hashes: Tuple[bytes, ...]


@dataclass
class Record:
    """What a run observed from the client's side; the harness turns it into
    the end-to-end metrics."""

    window_start: float = 0.0
    window_end: float = 0.0
    blocks: List[BlockSeen] = field(default_factory=list)
    due: Dict[bytes, float] = field(default_factory=dict)
    submitted: Dict[bytes, float] = field(default_factory=dict)
    refused: List[bytes] = field(default_factory=list)
    # the profiled slices of a traced run: (start, end, xplane path, tag)
    slices: List[Tuple[float, float, str, str]] = field(default_factory=list)

    def counted(self) -> List[BlockSeen]:
        """Only eras that complete inside the window count."""
        return [
            b
            for b in self.blocks
            if self.window_start < b.t_commit <= self.window_end
        ]

    def attempted(self) -> List[bytes]:
        """Transactions due in the window before its last complete era
        began: the ones that era could still have carried."""
        ends = [b.t_commit for b in self.counted()]
        if len(ends) < 2:
            return []
        return [
            h
            for h, due in self.due.items()
            if self.window_start <= due < ends[-2]
        ]

    def outstanding(self) -> int:
        """Attempted, accepted by the pool, and in no block yet."""
        held = {h for b in self.blocks for h in b.tx_hashes}
        refused = set(self.refused)
        return sum(
            1 for h in self.attempted() if h not in held and h not in refused
        )


class Traffic:
    def __init__(self, mix: dict, seed: int, chain_id: int, txs_per_block: int):
        self.mix = mix
        self.seed = seed
        self.chain_id = chain_id
        self.loop = mix["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"traffic: unknown loop {self.loop!r}")
        self.backlog = int(mix.get("backlog_blocks", 0) * txs_per_block)
        self.burst = int(mix["burst"])
        self._proc: Optional[subprocess.Popen] = None
        self._gaps = random.Random(seed ^ 0x5EED)
        self._next_due: Optional[float] = None

    def balances(self) -> Dict[bytes, int]:
        from lachain_tpu.crypto import ecdsa

        return {
            ecdsa.address_from_public_key(ecdsa.public_key_bytes(k)): 10**24
            for k in account_keys(self.seed, int(self.mix["accounts"]))
        }

    # -- the signer child -------------------------------------------------------
    def start(self) -> None:
        env = dict(os.environ, LACHAIN_TPU_BACKEND="native")
        self._proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "perfbench.signer",
                json.dumps(
                    {"mix": self.mix, "seed": self.seed, "chain_id": self.chain_id}
                ),
            ],
            cwd=str(ROOT),
            env=env,
            stdin=subprocess.DEVNULL,
            stdout=subprocess.PIPE,
        )
        try:
            import fcntl

            fcntl.fcntl(self._proc.stdout.fileno(), 1031, 1 << 20)  # F_SETPIPE_SZ
        except OSError:
            pass  # the default 64 KiB still holds ~350 transactions

    def take(self, count: int) -> list:
        """The stream's next `count` transactions, decoded from raw bytes."""
        from lachain_tpu.core.types import SignedTransaction

        out = []
        pipe = self._proc.stdout
        for _ in range(count):
            head = pipe.read(_FRAME.size)
            if len(head) < _FRAME.size:
                raise RuntimeError(
                    f"traffic: the signer ended (exit {self._proc.poll()})"
                )
            (size,) = _FRAME.unpack(head)
            out.append(SignedTransaction.decode(pipe.read(size)))
        return out

    def close(self) -> None:
        if self._proc is not None:
            self._proc.kill()
            self._proc.wait()
            self._proc.stdout.close()
            self._proc = None

    # -- arrivals ---------------------------------------------------------------
    def arrivals_until(self, t_load_start: float, now: float) -> List[float]:
        """Open loop: the due times in (last handed out, now], exponential
        gaps at the mix's rate from the seed."""
        rate = float(self.mix["rate_per_s"])
        if self._next_due is None:
            self._next_due = t_load_start + self._gaps.expovariate(rate)
        out = []
        while self._next_due <= now:
            out.append(self._next_due)
            self._next_due += self._gaps.expovariate(rate)
        return out

    def next_arrival(self) -> Optional[float]:
        return self._next_due


class Load:
    """What the drivers share: which transactions are due, handing them to
    the system, and the record of both."""

    def __init__(
        self,
        traffic: Traffic,
        record: Record,
        submit: Callable[[object], bool],
        backlog_now: Callable[[], int],
        clock: Callable[[], float],
    ):
        self.traffic = traffic
        self.record = record
        self._submit = submit
        self._backlog_now = backlog_now
        self._clock = clock
        self.t_start: Optional[float] = None
        self.stopped = False

    def start(self) -> None:
        self.t_start = self._clock()

    def due(self) -> List[float]:
        """Due times of what should be handed over now. Closed loop: enough
        to top the backlog up, due at this moment (the caller asks when a
        block has committed)."""
        if self.stopped:
            return []
        now = self._clock()
        if self.traffic.loop == "closed":
            return [now] * max(self.traffic.backlog - self._backlog_now(), 0)
        return self.traffic.arrivals_until(self.t_start, now)

    def hand_over(self, due_times: List[float]) -> None:
        for stx, due in zip(self.traffic.take(len(due_times)), due_times):
            h = stx.hash()
            self.record.due[h] = due
            self.record.submitted[h] = self._clock()
            if not self._submit(stx):
                self.record.refused.append(h)
