"""Blockbench's Smallbank contract as a WASM module for this VM.

The source is `benchmark/contracts/ethereum/smallbank.sol` of Blockbench
(Dinh et al., SIGMOD 2017): two mappings from a string, `savingStore` and
`checkingStore`, and six methods over them, Solidity 0.4 arithmetic
(unsigned, unchecked, modulo 2^256). No method reverts; an account that was
never written reads as zero. The module is ASSEMBLED here with
vm/builder.py, not emitted by a compiler: the framework ships no WASM
toolchain.

Interface, the source's: 4-byte keccak selector, then the arguments in the
contract ABI's head/tail layout that a Solidity contract is called with
(NOT vm/abi.py's flat layout, which puts a `bytes` argument in line): one
32-byte big-endian head word an argument, a uint256 in place, a string as
the offset (from the first head word) of its tail, which is a length word
and the string's bytes padded with zeros to a multiple of 32.

    almagate(string a, string b)          x = saving[a]; y = checking[b];
                                          checking[a] = 0; saving[b] = x + y
    getBalance(string a)                  returns saving[a] + checking[a]
    updateBalance(string a, uint256 v)    checking[a] += v
    updateSaving(string a, uint256 v)     saving[a] += v
    sendPayment(string a, string b,       x = checking[a]; y = checking[b];
                uint256 v)                checking[a] = x - v; checking[b] = y + v
    writeCheck(string a, uint256 v)       checking[a] -= v, and 1 more when
                                          v < checking[a] + saving[a]

A balance is one 32-byte big-endian storage word of the contract under
keccak256(tag ‖ the account id's bytes), tag b"s" for saving and b"c" for
checking. Arithmetic runs on four little-endian i64 limbs, so every word is
byte-swapped on its way in and out of memory.

Bounds of this module, where the source has none: calldata of more than 512
bytes, an account id of more than 64, and an offset or a length that leaves
the calldata are traps (a failed receipt, nothing written).

Memory map: 0..511 calldata (selector, head words at 4, 36, 68, tails
behind them) | 512..576 key preimage (tag, id) | 640.. four key slots |
768.. four value slots | 896 the word 1 | 928 the word 0 | 960 the
calldata's size.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Sequence, Union

from .. import abi
from ..builder import I32, I64, ModuleBuilder, Op, call_const, selector_case

TAG_SAVING = b"s"
TAG_CHECKING = b"c"

SIGNATURES = {
    "almagate": "almagate(string,string)",
    "getBalance": "getBalance(string)",
    "updateBalance": "updateBalance(string,uint256)",
    "updateSaving": "updateSaving(string,uint256)",
    "sendPayment": "sendPayment(string,string,uint256)",
    "writeCheck": "writeCheck(string,uint256)",
}

WORD = 32
_CALLDATA_MAX = 512
_ID_MAX = 64
_ARG0, _ARG1, _ARG2 = 4, 36, 68
_PREIMAGE = 512
_K1, _K2, _K3, _K4 = 640, 672, 704, 736
_X, _Y, _Z, _T = 768, 800, 832, 864
_ONE, _ZERO = 896, 928
_SIZE = 960
_LIMBS_LOW_FIRST = (24, 16, 8, 0)


def selector(op: str) -> bytes:
    return abi.method_selector(SIGNATURES[op])


def account_id(account: Union[int, bytes]) -> bytes:
    """An account number as the source's driver names it: in decimal."""
    return str(account).encode() if isinstance(account, int) else bytes(account)


def encode_call(op: str, *args: Union[int, bytes]) -> bytes:
    """Calldata of one call in the head/tail layout; an account is given as
    its number or as the id's bytes."""
    signature = SIGNATURES[op]
    kinds = signature[signature.index("(") + 1 : -1].split(",")
    head, tail = b"", b""
    for kind, arg in zip(kinds, args, strict=True):
        if kind == "string":
            data = account_id(arg)
            head += (len(kinds) * WORD + len(tail)).to_bytes(WORD, "big")
            tail += len(data).to_bytes(WORD, "big")
            tail += data.ljust(-(-len(data) // WORD) * WORD, b"\0")
        else:
            head += (arg % (1 << 256)).to_bytes(WORD, "big")
    return selector(op) + head + tail


def _bswap64(t: int) -> list:
    """The i64 on the stack with its bytes reversed; `t` is a scratch i64
    local. Three rounds of mask-and-shift (no rotl: the translated tier
    inlines shifts and shims rotations)."""
    out = [Op.local_set(t)]
    for mask, shift in ((0x00FF00FF00FF00FF, 8), (0x0000FFFF0000FFFF, 16)):
        out += [
            Op.local_get(t), Op.i64_const(mask), Op.i64_and,
            Op.i64_const(shift), Op.i64_shl,
            Op.local_get(t), Op.i64_const(shift), Op.i64_shr_u,
            Op.i64_const(mask), Op.i64_and,
            Op.i64_or, Op.local_set(t),
        ]
    return out + [
        Op.local_get(t), Op.i64_const(32), Op.i64_shl,
        Op.local_get(t), Op.i64_const(32), Op.i64_shr_u,
        Op.i64_or,
    ]


def _limb(ptr: int, off: int, dst: int, t: int) -> list:
    """local dst = the limb at byte `off` of the big-endian word at local
    `ptr`, as a number."""
    return [Op.local_get(ptr), Op.i64_load(off, 0), *_bswap64(t), Op.local_set(dst)]


def _add_or_sub(b: ModuleBuilder, sub: bool) -> int:
    """(pa, pb, pd): word at pd = word at pa +/- word at pb, modulo 2^256.
    Limb by limb from the low end, the carry (borrow) in local 6. pd may be
    pa or pb: a limb is read before it is written."""
    pa, pb, pd, x, y, r, c, t = range(8)
    body = []
    for off in _LIMBS_LOW_FIRST:
        body += _limb(pa, off, x, t) + _limb(pb, off, y, t)
        if sub:
            body += [
                # r = x - y - borrow; borrow = x < y or x - y < borrow
                Op.local_get(x), Op.local_get(y), Op.i64_sub, Op.local_tee(r),
                Op.local_get(c), Op.i64_lt_u,
                Op.local_get(x), Op.local_get(y), Op.i64_lt_u, Op.i32_or,
                Op.local_get(r), Op.local_get(c), Op.i64_sub, Op.local_set(r),
            ]
        else:
            body += [
                # r = x + y + carry; carry = x + y < x or r < x + y
                Op.local_get(x), Op.local_get(y), Op.i64_add, Op.local_tee(y),
                Op.local_get(c), Op.i64_add, Op.local_tee(r),
                Op.local_get(y), Op.i64_lt_u,
                Op.local_get(y), Op.local_get(x), Op.i64_lt_u, Op.i32_or,
            ]
        body += [
            Op.i64_extend_i32_u, Op.local_set(c),
            Op.local_get(pd), Op.local_get(r), *_bswap64(t), Op.i64_store(off, 0),
        ]
    return b.add_function([I32, I32, I32], [], [I64] * 5, body)


def _less_than(b: ModuleBuilder) -> int:
    """(pa, pb) -> 1 if word at pa < word at pb, from the high limb down."""
    pa, pb, x, y, t = range(5)
    body = []
    for off in reversed(_LIMBS_LOW_FIRST):
        body += _limb(pa, off, x, t) + _limb(pb, off, y, t)
        body += [
            Op.local_get(x), Op.local_get(y), Op.i64_ne, Op.if_(),
            Op.local_get(x), Op.local_get(y), Op.i64_lt_u, Op.return_,
            Op.end,
        ]
    return b.add_function([I32, I32], [I32], [I64] * 3, body + [Op.i32_const(0)])


def _trap_if() -> list:
    return [Op.if_(), Op.unreachable, Op.end]


def _small(b: ModuleBuilder) -> int:
    """(p) -> the big-endian word at p as a number; a trap unless it is
    below 2^16 (an offset or a length inside the calldata is)."""
    p, y, t = range(3)
    return b.add_function([I32], [I32], [I64] * 2, [
        Op.local_get(p), Op.i64_load(0, 0), Op.local_get(p), Op.i64_load(8, 0),
        Op.i64_or, Op.local_get(p), Op.i64_load(16, 0), Op.i64_or,
        Op.i64_const(0), Op.i64_ne, *_trap_if(),
        Op.local_get(p), Op.i64_load(24, 0), *_bswap64(t), Op.local_tee(y),
        Op.i64_const(1 << 16), Op.i64_ge_u, *_trap_if(),
        Op.local_get(y), Op.i32_wrap_i64,
    ])


def _key(b: ModuleBuilder, keccak: int, small: int) -> int:
    """(tag, head, out): out = keccak256(tag ‖ the bytes of the string whose
    head word is at `head`). The string's tail has to lie inside the
    calldata and hold at most _ID_MAX bytes."""
    tag, head, out, p, n = range(5)
    size = [Op.i32_const(_SIZE), Op.i32_load()]
    return b.add_function([I32, I32, I32], [], [I32] * 2, [
        Op.local_get(head), Op.call(small), Op.i32_const(_ARG0), Op.i32_add,
        Op.local_tee(p), Op.i32_const(WORD), Op.i32_add, *size, Op.i32_gt_u,
        *_trap_if(),
        Op.local_get(p), Op.call(small), Op.local_tee(n),
        Op.i32_const(_ID_MAX), Op.i32_gt_u, *_trap_if(),
        Op.local_get(p), Op.i32_const(WORD), Op.i32_add, Op.local_get(n),
        Op.i32_add, *size, Op.i32_gt_u, *_trap_if(),
        Op.i32_const(_PREIMAGE), Op.local_get(tag), Op.i32_store8(),
        Op.i32_const(_PREIMAGE + 1), Op.local_get(p), Op.i32_const(WORD),
        Op.i32_add, Op.local_get(n), Op.memory_copy,
        Op.i32_const(_PREIMAGE), Op.local_get(n), Op.i32_const(1), Op.i32_add,
        Op.local_get(out), Op.call(keccak),
    ])


@lru_cache(maxsize=1)
def code() -> bytes:
    """The module's bytes (the same on every call: the deploy transaction's
    payload, and through it the contract's address, depend on them)."""
    b = ModuleBuilder()
    call_size = b.add_import("env", "get_call_size", [], [I32])
    copy_call = b.add_import("env", "copy_call_value", [I32, I32, I32], [])
    load_st = b.add_import("env", "load_storage", [I32, I32], [])
    save_st = b.add_import("env", "save_storage", [I32, I32], [])
    set_ret = b.add_import("env", "set_return", [I32, I32], [])
    keccak = b.add_import("env", "crypto_keccak256", [I32, I32, I32], [])
    b.add_memory(1)
    b.add_data(_ONE + WORD - 1, b"\x01")
    add = _add_or_sub(b, sub=False)
    sub = _add_or_sub(b, sub=True)
    less = _less_than(b)
    key = _key(b, keccak, _small(b))
    saving, checking = TAG_SAVING[0], TAG_CHECKING[0]

    def case(op: str, *steps: Union[bytes, Sequence]) -> bytes:
        return selector_case(selector(op), list(steps))

    body = [
        # calldata to 0.., its size to _SIZE
        Op.call(call_size), Op.local_tee(0), Op.i32_const(_CALLDATA_MAX),
        Op.i32_gt_u, *_trap_if(),
        Op.i32_const(_SIZE), Op.local_get(0), Op.i32_store(),
        Op.i32_const(0), Op.local_get(0), Op.i32_const(0), Op.call(copy_call),
        case(
            "almagate",
            call_const(key, saving, _ARG0, _K1), call_const(key, checking, _ARG1, _K2),
            call_const(key, checking, _ARG0, _K3), call_const(key, saving, _ARG1, _K4),
            call_const(load_st, _K1, _X), call_const(load_st, _K2, _Y),
            call_const(save_st, _K3, _ZERO),
            call_const(add, _X, _Y, _Z), call_const(save_st, _K4, _Z),
        ),
        case(
            "getBalance",
            call_const(key, saving, _ARG0, _K1), call_const(key, checking, _ARG0, _K2),
            call_const(load_st, _K1, _X), call_const(load_st, _K2, _Y),
            call_const(add, _X, _Y, _Z), call_const(set_ret, _Z, WORD),
        ),
        case(
            "updateBalance",
            call_const(key, checking, _ARG0, _K1), call_const(load_st, _K1, _X),
            call_const(add, _X, _ARG1, _Z), call_const(save_st, _K1, _Z),
        ),
        case(
            "updateSaving",
            call_const(key, saving, _ARG0, _K1), call_const(load_st, _K1, _X),
            call_const(add, _X, _ARG1, _Z), call_const(save_st, _K1, _Z),
        ),
        case(
            "sendPayment",
            call_const(key, checking, _ARG0, _K1), call_const(key, checking, _ARG1, _K2),
            call_const(load_st, _K1, _X), call_const(load_st, _K2, _Y),
            call_const(sub, _X, _ARG2, _X), call_const(add, _Y, _ARG2, _Y),
            call_const(save_st, _K1, _X), call_const(save_st, _K2, _Y),
        ),
        case(
            "writeCheck",
            call_const(key, checking, _ARG0, _K1), call_const(key, saving, _ARG0, _K2),
            call_const(load_st, _K1, _X), call_const(load_st, _K2, _Y),
            call_const(add, _X, _Y, _Z), call_const(sub, _X, _ARG1, _T),
            call_const(less, _ARG1, _Z), Op.if_(),
            call_const(sub, _T, _ONE, _T),
            Op.end,
            call_const(save_st, _K1, _T),
        ),
        Op.unreachable,  # no such method
    ]
    b.add_function([], [], [I32], body, export="start")
    return b.build()
