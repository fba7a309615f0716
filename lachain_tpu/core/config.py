"""Node configuration: versioned JSON with sequential schema migrations.

Parity with the reference's ConfigManager
(/root/reference/src/Lachain.Core/Config/ConfigManager.cs:15-78): a config
file carries a `version` field; loading runs every migration from the file's
version up to CURRENT_VERSION in order, so operators can carry configs
across releases. Typed section accessors replace the reference's section
classes (NetworkConfig, GenesisConfig, VaultConfig, HardforkConfig...).
"""
from __future__ import annotations

import copy
import json
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Union

CURRENT_VERSION = 7

# "not scheduled yet" sentinel for migrated hardfork heights: far above any
# realistic chain height, so is_active() stays False until the operator
# coordinates a real activation height across the validator set
HARDFORK_HEIGHT_NEVER = 2**62

# (section, key) pairs older configs carry and this program ignores: the
# lane count and merkle worker count of the threaded execution and freeze,
# which are gone; every block executes and freezes on one path
RETIRED_KEYS = (("execution", "lanes"), ("execution", "merkleWorkers"))

# -- migrations --------------------------------------------------------------
# each migrates version N -> N+1 (reference runs 17 of these sequentially)

_MIGRATIONS: Dict[int, Callable[[dict], dict]] = {}


def _migration(frm: int):
    def deco(fn):
        _MIGRATIONS[frm] = fn
        return fn

    return deco


@_migration(1)
def _v1_to_v2(cfg: dict) -> dict:
    # v2 split the flat "port" into a network section
    net = cfg.setdefault("network", {})
    if "port" in cfg:
        net.setdefault("port", cfg.pop("port"))
    net.setdefault("host", "127.0.0.1")
    return cfg


@_migration(2)
def _v2_to_v3(cfg: dict) -> dict:
    # v3 added staking cycle parameters and the hardfork section
    staking = cfg.setdefault("staking", {})
    staking.setdefault("cycleDuration", 1000)
    staking.setdefault("vrfSubmissionPhase", 500)
    cfg.setdefault("hardfork", {})
    return cfg


@_migration(3)
def _v3_to_v4(cfg: dict) -> dict:
    # v4 (round 4, gossip peer discovery): an explicit dialable address for
    # wildcard binds / NAT — None keeps the bind host
    cfg.setdefault("network", {}).setdefault("advertiseHost", None)
    return cfg


@_migration(4)
def _v4_to_v5(cfg: dict) -> dict:
    # v5 (round 4, on-chain attendance detection): the detection-window
    # length joined the consensus-critical cycle parameters. The default
    # scales with the config's OWN cycle (same formula keygen uses) so a
    # short-cycle chain never gets a window that outlives the cycle
    staking = cfg.setdefault("staking", {})
    cycle = int(staking.get("cycleDuration", 1000))
    staking.setdefault(
        "attendanceDetectionDuration", max(min(100, cycle // 5), 1)
    )
    return cfg


@_migration(5)
def _v5_to_v6(cfg: dict) -> dict:
    # v6 (round 4, fast_wasm_gas hardfork): configs carry the repricing
    # height explicitly. A MIGRATED config belongs to a chain that ran
    # under the old gas schedule, so defaulting to 0 would retroactively
    # reprice historical blocks and break resync-from-genesis validation.
    # Default to the far-future sentinel: the old schedule stays in force
    # until the operator coordinates an explicit upgrade height. Configs
    # generated fresh at v6 (cli.py keygen) write fast_wasm_gas: 0
    # explicitly, so they never hit this default.
    hf = cfg.setdefault("hardfork", {})
    hf.setdefault("heights", {}).setdefault(
        "fast_wasm_gas", HARDFORK_HEIGHT_NEVER
    )
    return cfg


@_migration(6)
def _v6_to_v7(cfg: dict) -> dict:
    # v7 (round 6): the default storage engine flipped to the native LSM.
    # A MIGRATED config belongs to a chain whose database was written by
    # sqlite; the two on-disk formats are not interchangeable, so flipping
    # it silently would abandon the existing chain and resync a fresh LSM
    # store from genesis. Pin what the config was actually running. Fresh
    # v7 configs (cli.py keygen) write engine: "lsm" explicitly.
    cfg.setdefault("storage", {}).setdefault("engine", "sqlite")
    return cfg


def migrate(cfg: dict) -> dict:
    cfg = copy.deepcopy(cfg)
    version = int(cfg.get("version", 1))
    if version > CURRENT_VERSION:
        raise ValueError(
            f"config version {version} is newer than supported "
            f"{CURRENT_VERSION}"
        )
    if version == 5:
        # a config SAVED at v5 belongs to a chain that ran round-4
        # software, whose builds activated fast_wasm_gas from genesis.
        # The v5->v6 migration default (the NEVER sentinel, correct for
        # pre-round-4 configs) would silently DEACTIVATE the repricing on
        # such a chain and fork it from peers on the next resync. There
        # is no safe guess, so refuse until the operator states the
        # height explicitly (DEPLOY.md "Upgrading v5 configs").
        heights = (cfg.get("hardfork") or {}).get("heights") or {}
        if "fast_wasm_gas" not in heights:
            raise ValueError(
                "refusing to migrate a version-5 config without an "
                "explicit hardfork.heights.fast_wasm_gas: round-4 nodes "
                "activated the repricing at genesis and the migration "
                "default (never) would silently deactivate it. Set the "
                "height this chain actually activated at (0 for round-4 "
                "devnets) — see DEPLOY.md, 'Upgrading v5 configs'."
            )
    while version < CURRENT_VERSION:
        step = _MIGRATIONS.get(version)
        if step is None:
            raise ValueError(f"no migration from config version {version}")
        cfg = step(cfg)
        version += 1
        cfg["version"] = version
    return cfg


# -- typed sections ----------------------------------------------------------


@dataclass
class NetworkSection:
    host: str = "127.0.0.1"
    port: int = 7070
    # the address OTHER nodes should dial (defaults to host; set when
    # binding a wildcard or behind NAT in multi-host deployments)
    advertise_host: Optional[str] = None
    # public relay(s) — NAT'd nodes with no dialable address participate
    # through one (reference Hub relay bootstrap). A single "host:port:pubhex"
    # string or a LIST of them: the node registers with the first and fails
    # over down the list when its relay stops answering (relay HA)
    relay: Optional[Union[str, List[str]]] = None
    # peers: list of "host:port:pubkeyhex"
    peers: List[str] = field(default_factory=list)


@dataclass
class GenesisSection:
    chain_id: int = 225
    balances: Dict[str, str] = field(default_factory=dict)  # hexaddr -> dec
    # trusted-dealer consensus key set (PublicConsensusKeys.encode() hex) +
    # this node's validator index (-1 = observer)
    consensus_keys: str = ""
    validator_index: int = -1


@dataclass
class VaultSection:
    path: str = "wallet.json"
    password: str = ""


@dataclass
class StakingSection:
    cycle_duration: int = 1000
    vrf_submission_phase: int = 500
    attendance_detection_duration: int = 100


@dataclass
class RpcSection:
    enabled: bool = True
    host: str = "127.0.0.1"
    port: int = 7071
    api_key: Optional[str] = None
    # compressed secp256k1 pubkey hex whose signature unlocks the private
    # RPC methods (reference config "apiKey" doubles as this; kept separate
    # here so the static header key and the signing identity can rotate
    # independently)
    auth_pubkey: Optional[str] = None


@dataclass
class BlockchainSection:
    target_txs_per_block: int = 1000
    target_block_time_ms: int = 1000
    # consensus era pipelining lookahead (DEPLOY.md "Consensus
    # pipelining"): 0 = strictly sequential eras; w >= 1 admits era e+w's
    # proposal/RBC while era e is still in decrypt/commit. Raises journal
    # retention and peak memory by ~w eras — turn off on memory-constrained
    # validators.
    pipeline_window: int = 0


@dataclass
class HardforkSection:
    # name -> activation height (see core/hardforks.py)
    heights: Dict[str, int] = field(default_factory=dict)


@dataclass
class NodeConfig:
    version: int
    network: NetworkSection
    genesis: GenesisSection
    vault: VaultSection
    staking: StakingSection
    rpc: RpcSection
    blockchain: BlockchainSection
    hardfork: HardforkSection
    raw: dict

    @property
    def storage_path(self) -> Optional[str]:
        return self.raw.get("storage", {}).get("path")

    @property
    def storage_engine(self) -> str:
        """"lsm" (the native C++ LSM engine, the default since v7) or
        "sqlite" (explicit opt-out). Configs migrated from <=v6 carry
        engine: "sqlite" pinned by the v6->v7 migration — their database
        was written by sqlite and the formats are not interchangeable.
        Unknown names are a hard error: silently falling back would
        rebuild a fresh chain from genesis on a typo."""
        engine = self.raw.get("storage", {}).get("engine", "lsm")
        if engine not in ("sqlite", "lsm"):
            raise ValueError(
                f"unknown storage.engine {engine!r} (use 'sqlite' or 'lsm')"
            )
        return engine

    @property
    def ignored_keys(self) -> List[str]:
        """Keys a config may still carry that the program no longer reads
        (RETIRED_KEYS); the file loads, and `run` says they do nothing."""
        return [
            f"{section}.{key}"
            for section, key in RETIRED_KEYS
            if key in (self.raw.get(section) or {})
        ]

    @property
    def trace_capacity(self) -> Optional[int]:
        """Flight-recorder ring capacity (events) for BOTH the Python span
        ring and the native engine rings. Optional and additive (no config
        version bump): absent means the LACHAIN_TRACE_CAPACITY env / the
        built-in default decides. 0 turns the recorder off, spans included."""
        cap = self.raw.get("observability", {}).get("traceCapacity")
        return None if cap is None else int(cap)

    @property
    def tx_sample_shift(self) -> Optional[int]:
        """Tx-lifecycle sampling (utils/txtrace.py): keep 1/2^shift of
        transactions (0 = stamp every tx). Optional and additive (no
        config version bump): absent keeps the built-in default. The
        sampling decision itself is a deterministic function of the tx
        hash, but the SHIFT must match fleet-wide for cross-node timelines
        to align (DEPLOY.md "Fleet observability")."""
        shift = self.raw.get("observability", {}).get("txSampleShift")
        return None if shift is None else int(shift)

    @property
    def network_region(self) -> Optional[str]:
        """This node's emulated/labelled WAN region (network.region).
        Optional and additive (no config version bump): used by the
        LinkShaper's region matrix and surfaced in fleet views; absent
        means unlabelled (treated as the shaper's first region when a
        shaper is installed by position)."""
        region = self.raw.get("network", {}).get("region")
        return None if region is None else str(region)

    @property
    def wan_shaper(self) -> Optional[str]:
        """WAN link-shaping spec (network.wanShaper), a LinkShaper.parse
        string like "regions=us,eu;default=40ms/5ms@4mbps;intra=1ms".
        Optional and additive (no config version bump): absent disables
        shaping. The SAME spec (and fault seed) must be installed
        fleet-wide for two-run determinism to hold (DEPLOY.md "WAN
        operations & rolling upgrades")."""
        spec = self.raw.get("network", {}).get("wanShaper")
        return None if spec is None else str(spec)

    @property
    def idle_alert_fraction(self) -> Optional[float]:
        """Idle-anatomy health alert (observability.idleAlertFraction):
        when the rolling era idle fraction from the flight recorder
        exceeds this value, /healthz reads degraded with an idle-fraction
        reason. Optional and additive (no config version bump): absent
        disables the alert."""
        frac = self.raw.get("observability", {}).get("idleAlertFraction")
        return None if frac is None else float(frac)

    @classmethod
    def from_dict(cls, cfg: dict) -> "NodeConfig":
        cfg = migrate(cfg)
        net = cfg.get("network", {})
        gen = cfg.get("genesis", {})
        vault = cfg.get("vault", {})
        staking = cfg.get("staking", {})
        rpc = cfg.get("rpc", {})
        bc = cfg.get("blockchain", {})
        hf = cfg.get("hardfork", {})
        return cls(
            version=cfg["version"],
            network=NetworkSection(
                host=net.get("host", "127.0.0.1"),
                port=int(net.get("port", 7070)),
                advertise_host=net.get("advertiseHost"),
                relay=net.get("relay"),
                peers=list(net.get("peers", [])),
            ),
            genesis=GenesisSection(
                chain_id=int(gen.get("chainId", 225)),
                balances=dict(gen.get("balances", {})),
                consensus_keys=gen.get("consensusKeys", ""),
                validator_index=int(gen.get("validatorIndex", -1)),
            ),
            vault=VaultSection(
                path=vault.get("path", "wallet.json"),
                password=vault.get("password", ""),
            ),
            staking=StakingSection(
                cycle_duration=int(staking.get("cycleDuration", 1000)),
                vrf_submission_phase=int(
                    staking.get("vrfSubmissionPhase", 500)
                ),
                attendance_detection_duration=int(
                    staking.get("attendanceDetectionDuration", 100)
                ),
            ),
            rpc=RpcSection(
                enabled=bool(rpc.get("enabled", True)),
                host=rpc.get("host", "127.0.0.1"),
                port=int(rpc.get("port", 7071)),
                api_key=rpc.get("apiKey"),
                auth_pubkey=rpc.get("authPubkey"),
            ),
            blockchain=BlockchainSection(
                target_txs_per_block=int(bc.get("targetTxsPerBlock", 1000)),
                target_block_time_ms=int(bc.get("targetBlockTimeMs", 1000)),
                pipeline_window=int(bc.get("pipelineWindow", 0)),
            ),
            hardfork=HardforkSection(
                heights={k: int(v) for k, v in hf.get("heights", {}).items()}
            ),
            raw=cfg,
        )

    @classmethod
    def load(cls, path: str) -> "NodeConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.raw, f, indent=2, sort_keys=True)
