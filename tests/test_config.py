"""Config migrations + hardfork flag system (reference ConfigManager.cs
sequential migrations, HardforkHeights.cs height gates)."""
import pytest

from lachain_tpu.core import hardforks
from lachain_tpu.core.config import CURRENT_VERSION, NodeConfig, migrate


def test_v1_config_migrates_all_the_way():
    cfg = NodeConfig.from_dict({"version": 1, "port": 9999})
    assert cfg.version == CURRENT_VERSION
    assert cfg.network.port == 9999
    assert cfg.staking.cycle_duration == 1000  # v3 default materialized


def test_newer_version_rejected():
    with pytest.raises(ValueError):
        migrate({"version": CURRENT_VERSION + 1})


def test_sections_parse_and_roundtrip(tmp_path):
    raw = {
        "version": CURRENT_VERSION,
        "network": {"host": "0.0.0.0", "port": 7070, "peers": ["a:1:00"]},
        "genesis": {"chainId": 97, "balances": {"0x" + "11" * 20: "5"}},
        "rpc": {"port": 7071, "apiKey": "sekrit"},
        "blockchain": {"targetBlockTimeMs": 250},
    }
    cfg = NodeConfig.from_dict(raw)
    assert cfg.genesis.chain_id == 97
    assert cfg.rpc.api_key == "sekrit"
    assert cfg.blockchain.target_block_time_ms == 250
    p = tmp_path / "c.json"
    cfg.save(str(p))
    again = NodeConfig.load(str(p))
    assert again.network.peers == ["a:1:00"]


def test_hardfork_flags():
    hardforks.reset_for_tests()
    try:
        hardforks.set_hardfork_heights({"strict_share_validation": 100})
        assert not hardforks.is_active("strict_share_validation", 99)
        assert hardforks.is_active("strict_share_validation", 100)
        with pytest.raises(RuntimeError):
            hardforks.set_hardfork_heights({})  # one-shot
        with pytest.raises(ValueError):
            hardforks.set_hardfork_heights({"bogus": 1}, force=True)
    finally:
        hardforks.reset_for_tests()


def test_round4_migrations_v3_to_v6():
    """The round-4 feature set carried three REAL migrations: advertiseHost
    (gossip discovery), attendanceDetectionDuration (on-chain attendance),
    and the fast_wasm_gas repricing height (first gas-schedule hardfork)."""
    from lachain_tpu.core.config import CURRENT_VERSION, migrate

    v3 = {
        "version": 3,
        "network": {"host": "1.2.3.4", "port": 9},
        "staking": {"cycleDuration": 50, "vrfSubmissionPhase": 20},
        "hardfork": {},
    }
    out = migrate(v3)
    assert out["version"] == CURRENT_VERSION == 7
    assert out["network"]["advertiseHost"] is None
    # scaled to the config's own short cycle (50 // 5), never >= the cycle
    assert out["staking"]["attendanceDetectionDuration"] == 10
    # migrated configs belong to chains that ran the OLD gas schedule:
    # silently activating from genesis would retroactively reprice history
    # and break resync validation, so the default is the NEVER sentinel
    # until the operator schedules a real activation height
    from lachain_tpu.core.config import HARDFORK_HEIGHT_NEVER

    assert out["hardfork"]["heights"]["fast_wasm_gas"] == HARDFORK_HEIGHT_NEVER
    # values an operator already set are never clobbered
    v5 = {
        "version": 5,
        "hardfork": {"heights": {"fast_wasm_gas": 12345}},
    }
    assert migrate(v5)["hardfork"]["heights"]["fast_wasm_gas"] == 12345


def test_v6_to_v7_storage_engine_migration():
    """Round 6 flipped the default engine to LSM — but ONLY for fresh
    configs. A migrated <=v6 config's database was written by sqlite and
    the formats are not interchangeable, so the migration pins sqlite;
    flipping it silently would abandon the chain and resync from genesis."""
    out = migrate({"version": 6})
    assert out["version"] == CURRENT_VERSION
    assert out["storage"]["engine"] == "sqlite"
    # the pin follows the whole chain from any pre-v7 version
    assert migrate({"version": 1, "port": 1})["storage"]["engine"] == "sqlite"
    # an operator's explicit choice is never clobbered
    v6 = {"version": 6, "storage": {"engine": "lsm"}}
    assert migrate(v6)["storage"]["engine"] == "lsm"
    # fresh v7 configs default to the native engine
    assert NodeConfig.from_dict({"version": 7}).storage_engine == "lsm"


@pytest.mark.parametrize(
    "execution",
    [{"lanes": 8}, {"merkleWorkers": 8}, {"lanes": 8, "merkleWorkers": 8}],
    ids=["lanes", "merkleWorkers", "both"],
)
def test_a_retired_execution_key_loads_warns_once_and_commits_serially(
    tmp_path, caplog, monkeypatch, execution
):
    """`execution.lanes` and `execution.merkleWorkers` chose threaded routes
    that are gone. A config that still sets them is an operator's file: it
    loads, the node it builds commits blocks on the one path, and the
    start-up log says once which keys do nothing."""
    import asyncio
    import json
    import logging
    import random

    from lachain_tpu import cli
    from lachain_tpu.consensus.keys import trusted_key_gen
    from lachain_tpu.core import execution as ex
    from lachain_tpu.core import system_contracts as sc
    from lachain_tpu.core.types import (
        BlockHeader,
        MultiSig,
        Transaction,
        sign_transaction,
        tx_merkle_root,
    )
    from lachain_tpu.core.vault import PrivateWallet
    from lachain_tpu.crypto import ecdsa

    class Rng:
        def __init__(self, seed):
            self._r = random.Random(seed)

        def randbelow(self, n):
            return self._r.randrange(n)

    # _build_node sets the staking cycle globals: restore them afterwards
    for name in ("CYCLE_DURATION", "VRF_SUBMISSION_PHASE", "ATTENDANCE_DETECTION_DURATION"):
        monkeypatch.setattr(sc, name, getattr(sc, name))
    chain = 97
    pub, _privs = trusted_key_gen(4, 1, rng=Rng(2))
    user = ecdsa.generate_private_key(Rng(3))
    sender = ecdsa.address_from_public_key(ecdsa.public_key_bytes(user))
    wallet = str(tmp_path / "wallet.json")
    PrivateWallet(ecdsa_priv=ecdsa.generate_private_key(Rng(4)), path=wallet).save()
    path = tmp_path / "config.json"
    path.write_text(json.dumps({
        "version": CURRENT_VERSION,
        "genesis": {
            "chainId": chain,
            "consensusKeys": pub.encode().hex(),
            "validatorIndex": -1,
            "balances": {"0x" + sender.hex(): str(10**18)},
        },
        "vault": {"path": wallet},
        "execution": execution,
    }))
    cfg = NodeConfig.load(str(path))
    keys = [f"execution.{k}" for k in execution]
    assert cfg.ignored_keys == keys

    async def build_and_commit():
        with caplog.at_level(logging.WARNING, logger="lachain_tpu.cli"):
            node, _peers = cli._build_node(cfg)
        bm = node.block_manager
        for height in (1, 2):
            txs = bm.order_transactions([
                sign_transaction(
                    Transaction(to=b"\x42" * 20, value=5, nonce=2 * (height - 1) + i,
                                gas_price=1, gas_limit=21000),
                    user, chain,
                )
                for i in range(2)
            ], chain)
            em = bm.emulate(txs, height)
            header = BlockHeader(
                index=height, prev_block_hash=bm.block_by_height(height - 1).hash(),
                merkle_root=tx_merkle_root([t.hash() for t in txs]),
                state_hash=em.state_hash, nonce=height,
            )
            bm.execute_block(header, txs, MultiSig(()))
        return node

    node = asyncio.run(build_and_commit())
    assert node.block_manager.current_height() == 2
    assert ex.get_balance(node.state.new_snapshot(), b"\x42" * 20) == 20
    warned = [r.getMessage() for r in caplog.records if r.name == "lachain_tpu.cli"]
    assert len(warned) == 1, warned
    assert all(key in warned[0] for key in keys)
    assert "no effect" in warned[0]
