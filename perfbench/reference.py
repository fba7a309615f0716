"""The plain references and the comparisons that decide `correct`.

* The chain: every committed block is executed again, in order from
  genesis, one transaction after another on a fresh in-memory store, and
  must give the state root its header carries, with every receipt a
  success. (The program's own emulate() is not used: its process-wide memo
  would answer from the run under test.)
* The ledger, with no executor at all: every committed transaction is one
  the generator sent, sits in exactly one block, continues its sender's
  nonces without a gap, and what the recipients hold is what was sent to
  them — compared with what each store reads back.
* The era batch: the last TPKE era batch the backend proxy kept gives, slot
  for slot, the same combined points through HostEraPipeline.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple


def reexecute(
    chain_id: int,
    balances: Dict[bytes, int],
    validator_pubs: Sequence[bytes],
    blocks: Iterable[Tuple[object, list]],
) -> List[str]:
    """blocks: (Block, its transactions in the block's order), ascending from
    height 1. Returns what went wrong; empty when every state root matches."""
    from lachain_tpu.core import system_contracts
    from lachain_tpu.core.block_manager import BlockManager
    from lachain_tpu.storage.kv import MemoryKV
    from lachain_tpu.storage.state import StateManager

    kv = MemoryKV()
    state = StateManager(kv)
    executer = system_contracts.make_executer(chain_id)
    BlockManager(kv, state, executer, lanes=1).build_genesis(
        dict(balances), chain_id, validator_pubs=list(validator_pubs)
    )
    wrong = []
    for block, txs in blocks:
        index = block.header.index
        snap = state.new_snapshot(state.committed)
        for i, stx in enumerate(txs):
            if not executer.execute(snap, stx, index, i).ok:
                wrong.append(f"block {index}: transaction {i} failed in the reference")
        roots = snap.freeze()
        if roots.state_hash() != block.header.state_hash:
            wrong.append(
                f"block {index}: reference state root {roots.state_hash().hex()[:16]} "
                f"!= header {block.header.state_hash.hex()[:16]}"
            )
            return wrong  # later roots build on this one
        state.commit(index, roots)
    return wrong


def ledger(
    chain_id: int, blocks: Iterable[Tuple[object, list]], sent: Dict[bytes, object]
) -> Tuple[List[str], Dict[bytes, int], Dict[bytes, int]]:
    """(what went wrong, balance each recipient must hold, nonce each sender
    must have) from the committed blocks alone."""
    wrong: List[str] = []
    seen = set()
    nonces: Dict[bytes, int] = {}
    credit: Dict[bytes, int] = {}
    for block, txs in blocks:
        for stx in txs:
            h = stx.hash()
            if h not in sent:
                wrong.append(f"block {block.header.index} holds a transaction nobody sent")
            if h in seen:
                wrong.append(f"transaction {h.hex()[:16]} is in two blocks")
            seen.add(h)
            sender = stx.sender(chain_id)
            if stx.tx.nonce != nonces.get(sender, 0):
                wrong.append(
                    f"block {block.header.index}: nonce gap for {sender.hex()[:12]}"
                )
            nonces[sender] = stx.tx.nonce + 1
            credit[stx.tx.to] = credit.get(stx.tx.to, 0) + stx.tx.value
    return wrong, credit, nonces


def read_back(state, block_manager, block, credit, nonces) -> List[str]:
    """What one store holds against the ledger: each transaction of `block`
    and its receipt are there; balances and nonces are as `ledger` says
    (call with the last block once, the balances being cumulative)."""
    from lachain_tpu.core.execution import get_balance, get_nonce

    wrong = []
    for h in block.tx_hashes:
        if block_manager.transaction_by_hash(h) is None:
            wrong.append(f"transaction {h.hex()[:16]} not read back")
        if block_manager.receipt_by_hash(h) is None:
            wrong.append(f"receipt {h.hex()[:16]} not read back")
    if credit is not None:
        snap = state.new_snapshot()
        for addr, want in credit.items():
            if get_balance(snap, addr) != want:
                wrong.append(f"recipient {addr.hex()[:12]} balance differs")
        for addr, want in nonces.items():
            if get_nonce(snap, addr) != want:
                wrong.append(f"sender {addr.hex()[:12]} nonce differs")
    return wrong


def era_batch_sides(proxy):
    """(device side, host side) for the kept batch: the configuration's own
    device pipeline with everything routed to it, and HostEraPipeline."""
    from lachain_tpu.crypto.native_backend import NativeBackend
    from lachain_tpu.crypto.tpu_backend import TpuBackend
    from lachain_tpu.ops.verify import HostEraPipeline

    host = NativeBackend()
    device_side = TpuBackend(
        host_backend=host, pipeline=proxy._get_pipeline(), min_device_lanes=1
    )
    host_side = TpuBackend(
        host_backend=host, pipeline=HostEraPipeline(host), min_device_lanes=1
    )
    return device_side, host_side


def check_kept_batch(proxy) -> List[str]:
    """The kept batch's answers, as the run under test got them, against
    HostEraPipeline: all slots accepted, combined points equal."""
    from lachain_tpu.crypto import bls12381 as bls

    if proxy.last_era_batch is None:
        return ["no TPKE era batch reached the backend"]
    jobs, vks, got = proxy.last_era_batch
    _device, host_side = era_batch_sides(proxy)
    want = host_side.tpke_era_verify_combine(jobs, vks)
    wrong = []
    for i, (g, w) in enumerate(zip(got, want)):
        if not (g[0] and w[0] and bls.g1_eq(g[1], w[1])):
            wrong.append(f"kept era batch: slot {i} differs from HostEraPipeline")
    return wrong
