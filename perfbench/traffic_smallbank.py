"""The Smallbank mixes' transaction stream (`tx.kind` "smallbank"): calls of
the one contract lachain_tpu/vm/contracts/smallbank.py, drawn from --seed as
the mix's `bank` section says, and before them the transaction that deploys
it. traffic.py's generator knows transfers only and its signer child refuses
any other kind, so the stream and its signer live here; everything else of a
mix (loop, backlog, burst, arrivals, the record) is traffic.Traffic's.

The stream is fixed by the seed: transaction k is signed by client key
k mod A with nonce k div A and gas price 1 + k mod cycle, as the transfer
mixes'. Transaction 0 is the deployment, sent to
system_contracts.DEPLOY_ADDRESS by key 0 with nonce 0, so the contract's
address is known before it exists; every later transaction is a call.

Run as a module (`python -m perfbench.traffic_smallbank <spec>`) this file is
the signer child: it writes the stream to stdout as length-prefixed raw
transactions until the pipe closes, on the native host backend.
"""
from __future__ import annotations

import bisect
import itertools
import json
import os
import random
import subprocess
import sys
from typing import Iterator, Tuple

if __name__ == "__main__":
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

from perfbench import reference_smallbank  # noqa: E402
from perfbench.spec import ROOT  # noqa: E402
from perfbench.traffic import _FRAME, Traffic, account_keys  # noqa: E402

# operation -> (how many leading arguments are account ids, whether an
# amount follows them), from the reference's table of the six signatures
ACCOUNT_ARGS = {
    sig.split("(")[0]: (ids, words > ids)
    for sig, (words, ids) in reference_smallbank.SIGNATURES.items()
}


class Zipf:
    """Ranks 0..n-1 with P(r) proportional to 1 / (r + 1)^constant, drawn by
    exact inverse CDF (YCSB's generator approximates the same law)."""

    def __init__(self, n: int, constant: float):
        self.cdf = list(
            itertools.accumulate((r + 1) ** -constant for r in range(n))
        )

    def mass(self, top: int) -> float:
        """The closed form's probability of the `top` hottest ranks."""
        return self.cdf[top - 1] / self.cdf[-1]

    def draw(self, rng: random.Random) -> int:
        at = bisect.bisect_right(self.cdf, rng.random() * self.cdf[-1])
        return min(at, len(self.cdf) - 1)  # the product may round up to the total


def operations(mix: dict, seed: int) -> Iterator[Tuple[str, Tuple[int, ...]]]:
    """(operation, arguments), endlessly, as the mix's `bank` section says:
    the operation by weight, each account id Zipfian and independent of the
    others, the amount uniform."""
    bank = mix["bank"]
    rng = random.Random(seed * 1_000_003 + 0xBA2C)
    zipf = Zipf(int(bank["accounts"]), float(bank["zipf_constant"]))
    names = list(bank["weights"])
    edges = list(itertools.accumulate(float(bank["weights"][n]) for n in names))
    lo, hi = int(bank["amount_min"]), int(bank["amount_max"])
    while True:
        name = names[bisect.bisect_right(edges, rng.random() * edges[-1])]
        ids, amount = ACCOUNT_ARGS[name]
        args = [zipf.draw(rng) for _ in range(ids)]
        if amount:
            args.append(rng.randint(lo, hi))
        yield name, tuple(args)


def deployer(seed: int, accounts: int) -> bytes:
    from lachain_tpu.crypto import ecdsa

    key = account_keys(seed, accounts)[0]
    return ecdsa.address_from_public_key(ecdsa.public_key_bytes(key))


def contract_address(mix: dict, seed: int) -> bytes:
    """Where transaction 0 puts the contract."""
    from lachain_tpu.vm.vm import contract_address as deployed_at

    return deployed_at(deployer(seed, int(mix["accounts"])), 0)


def signed_stream(mix: dict, seed: int, chain_id: int) -> Iterator[bytes]:
    """Raw signed transactions, endlessly: the deployment, then the calls."""
    from lachain_tpu.core import system_contracts
    from lachain_tpu.core.types import Transaction, sign_transaction
    from lachain_tpu.utils.serialization import write_bytes
    from lachain_tpu.vm.contracts import smallbank

    tx = mix["tx"]
    if tx["kind"] != "smallbank":
        raise ValueError(f"traffic_smallbank: transaction kind {tx['kind']!r}")
    keys = account_keys(seed, int(mix["accounts"]))
    contract = contract_address(mix, seed)
    cycle, gas_limit = int(tx["gas_price_cycle"]), int(tx["gas_limit"])
    deployment = (
        system_contracts.DEPLOY_ADDRESS,
        system_contracts.SEL_DEPLOY + write_bytes(smallbank.code()),
    )
    calls = (
        (contract, smallbank.encode_call(name, *args))
        for name, args in operations(mix, seed)
    )
    for k, (to, invocation) in enumerate(itertools.chain([deployment], calls)):
        yield sign_transaction(
            Transaction(
                to=to,
                value=0,
                nonce=k // len(keys),
                gas_price=1 + (k % cycle),
                gas_limit=gas_limit,
                invocation=invocation,
            ),
            keys[k % len(keys)],
            chain_id,
        ).encode()


class SmallbankTraffic(Traffic):
    """traffic.Traffic with this module as its signer child."""

    def start(self) -> None:
        # Traffic.start with the child's module line changed
        self._proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "perfbench.traffic_smallbank",
                json.dumps(
                    {"mix": self.mix, "seed": self.seed, "chain_id": self.chain_id}
                ),
            ],
            cwd=str(ROOT),
            env=dict(os.environ, LACHAIN_TPU_BACKEND="native"),
            stdin=subprocess.DEVNULL,
            stdout=subprocess.PIPE,
        )
        try:
            import fcntl

            fcntl.fcntl(self._proc.stdout.fileno(), 1031, 1 << 20)  # F_SETPIPE_SZ
        except OSError:
            pass  # the default 64 KiB still holds ~250 calls


def main() -> None:
    spec = json.loads(sys.argv[1])
    out = sys.stdout.buffer
    try:
        for raw in signed_stream(spec["mix"], spec["seed"], spec["chain_id"]):
            out.write(_FRAME.pack(len(raw)) + raw)
    except BrokenPipeError:
        os._exit(0)  # the benchmark closed its end: done


if __name__ == "__main__":
    main()
