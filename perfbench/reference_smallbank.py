"""The plain reference of the Smallbank cell: two dicts and six functions
over integers modulo 2^256, written from the words of
perfbench/configs/hb7-smallbank.json (`schema.operations`), not from the
contract's code. It knows no VM: nothing here imports lachain_tpu.vm or
core.execution; calldata is decoded by the few lines below (4-byte
keccak selector, then one 32-byte big-endian head word an argument: a
uint256 in place, a string as the offset of its length word and bytes).
An account is the id string's bytes, as the source's mapping keys it.

`correct` in that cell holds only if, after the committed transactions have
been applied here in block and in-block order, every account they touched
reads back this model's `saving` and `checking` from the contract's storage
in every store, and every committed getBalance's receipt carries this
model's sum at that position.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from lachain_tpu.crypto.hashes import keccak256

M = 1 << 256
WORD = 32
TAG_SAVING, TAG_CHECKING = b"s", b"c"

# signature -> (arguments, how many of them, the first ones, are account ids;
# what follows is an amount)
SIGNATURES = {
    "almagate(string,string)": (2, 2),
    "getBalance(string)": (1, 1),
    "updateBalance(string,uint256)": (2, 1),
    "updateSaving(string,uint256)": (2, 1),
    "sendPayment(string,string,uint256)": (3, 2),
    "writeCheck(string,uint256)": (2, 1),
}
_BY_SELECTOR = {
    keccak256(sig.encode())[:4]: (sig.split("(")[0], words, ids)
    for sig, (words, ids) in SIGNATURES.items()
}


def account_id(number: int) -> bytes:
    """Account `number` as the driver names it: the decimal string."""
    return str(number).encode()


def decode(calldata: bytes) -> Tuple[str, tuple, int]:
    """(operation, its arguments, how many of them are account ids) of one
    call's calldata; an id is the string's bytes, an amount an integer."""
    name, words, ids = _BY_SELECTOR[calldata[:4]]
    body = calldata[4:]

    def word(at: int) -> int:
        if at + WORD > len(body):
            raise ValueError(f"{name}: calldata of {len(calldata)} bytes")
        return int.from_bytes(body[at : at + WORD], "big")

    args = []
    for i in range(words):
        head = word(i * WORD)
        if i < ids:
            size = word(head)
            if head + WORD + size > len(body):
                raise ValueError(f"{name}: a string of {size} bytes at {head}")
            args.append(body[head + WORD : head + WORD + size])
        else:
            args.append(head)
    return name, tuple(args), ids


def storage_key(tag: bytes, account: bytes) -> bytes:
    """Where one balance lives in the contract's storage."""
    return keccak256(tag + account)


class Bank:
    def __init__(self) -> None:
        self.saving: Dict[bytes, int] = {}
        self.checking: Dict[bytes, int] = {}
        self.touched: Set[bytes] = set()

    # -- the six operations, as the configuration's file words them -------------
    def almagate(self, a: bytes, b: bytes) -> None:
        x = self.saving.get(a, 0)
        y = self.checking.get(b, 0)
        self.checking[a] = 0
        self.saving[b] = (x + y) % M

    def getBalance(self, a: bytes) -> int:
        return (self.saving.get(a, 0) + self.checking.get(a, 0)) % M

    def updateBalance(self, a: bytes, v: int) -> None:
        self.checking[a] = (self.checking.get(a, 0) + v) % M

    def updateSaving(self, a: bytes, v: int) -> None:
        self.saving[a] = (self.saving.get(a, 0) + v) % M

    def sendPayment(self, a: bytes, b: bytes, v: int) -> None:
        x = self.checking.get(a, 0)
        y = self.checking.get(b, 0)
        self.checking[a] = (x - v) % M
        self.checking[b] = (y + v) % M

    def writeCheck(self, a: bytes, v: int) -> None:
        x = self.checking.get(a, 0)
        y = self.saving.get(a, 0)
        covered = v < (x + y) % M
        self.checking[a] = (x - v - (1 if covered else 0)) % M

    # -- replay -----------------------------------------------------------------
    def apply(self, calldata: bytes) -> Optional[int]:
        """One committed call; what getBalance returns, None for the rest."""
        name, args, ids = decode(calldata)
        self.touched.update(args[:ids])
        return getattr(self, name)(*args)

    def balances(self, accounts: Sequence[bytes]) -> List[List[int]]:
        """[saving, checking] of each account, zero where never written."""
        return [
            [self.saving.get(a, 0), self.checking.get(a, 0)] for a in accounts
        ]


def replay(calls: Iterable[Tuple[bytes, bytes]]) -> Tuple[Bank, List[Tuple[bytes, int]]]:
    """calls: (transaction hash, calldata) in block and in-block order.
    Returns the bank afterwards and, for each getBalance, (hash, the sum it
    must have returned)."""
    bank = Bank()
    returns: List[Tuple[bytes, int]] = []
    for tx_hash, calldata in calls:
        got = bank.apply(calldata)
        if got is not None:
            returns.append((tx_hash, got))
    return bank, returns


def compare(
    store: str, want_words: List[List[int]], want_returns: List[int], report: dict
) -> List[str]:
    """One store's report ({"words": [[saving, checking], ...], "returns":
    [int or None, ...]}, in the order asked) against the model. Zero
    tolerance: integers."""
    wrong = []
    if report["words"] != want_words:
        bad = sum(g != w for g, w in zip(report["words"], want_words))
        wrong.append(
            f"{store}: {bad} of {len(want_words)} touched accounts read back "
            f"another saving or checking balance than the dict model holds"
        )
    if report["returns"] != want_returns:
        bad = sum(g != w for g, w in zip(report["returns"], want_returns))
        wrong.append(
            f"{store}: {bad} of {len(want_returns)} getBalance receipts carry "
            f"another sum than the dict model's at that position"
        )
    return wrong
