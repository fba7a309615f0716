"""lachain-tpu operator CLI: the runnable node process.

Parity with the reference's console
(/root/reference/src/Lachain.Console/Program.cs:23-47 verbs,
TrustedKeygen.cs:56-66 devnet generation, Application.cs:67-198 service
composition):

  lachain-tpu keygen --n 4 --f 1 --out netdir [--port-base 7070]
      trusted-dealer devnet generation: writes config{i}.json +
      wallet{i}.json for every validator, cross-wired as peers.
  lachain-tpu run --config netdir/config0.json
      boots a full node from a config: wallet, network, sync, RPC, and
      the autonomous era lifecycle.
  lachain-tpu height --config netdir/config0.json
      one-shot local status (height + validator set) without RPC.
  lachain-tpu db shrink|rollback|compact|export|import --config ...
      offline store maintenance (prune checkpoints / restore a snapshot /
      LSM full merge / engine-portable dump + load — the sqlite<->lsm
      migration path; reference `db` verb + --RollBackTo,
      Application.cs:119-127).
  lachain-tpu encrypt|decrypt --wallet ...
      wallet re-keying / decrypted inspection (reference encrypt/decrypt).
  lachain-tpu console --rpc http://127.0.0.1:7071
      interactive operator shell attached to a LIVE node over its RPC
      (role of the reference's in-process console, CLI/ConsoleManager.cs:14
      + ConsoleCommands.cs:20; attaching over RPC means the shell works
      against any reachable node, containers included).
  lachain-tpu chaos --drop 0.1 --crash 3@50:400 --partition 0,1|2,3@30:500
      seeded fault-injection run against an in-process devnet: eras under
      message loss / crash / partition schedules, with an era-by-era
      recovery report. Same seed -> same faults -> same chain, so a
      production failure replays from its seed (DEPLOY.md, Failure
      handling).
  lachain-tpu chaos --crash-point block.persist.mid
      storage crash scenario: a child process runs the deterministic
      commit workload and is SIGKILLed at the named pipeline point; the
      parent fscks the torn database, repairs, and verifies a resumed run
      completes (DEPLOY.md, Crash recovery).
  lachain-tpu fleet-upgrade --n 6 --wan 'regions=us,eu;default=40ms/5ms'
      zero-downtime rolling-upgrade drill: an in-process TCP fleet boots
      on the legacy (pre-handshake) wire, optionally WAN-shaped into
      emulated regions, and rolls node-by-node onto the LTRX versioned
      wire under paced traffic. Gated on /healthz staying ok and zero
      fleet missed eras; prints one JSON result line
      (DEPLOY.md, WAN operations & rolling upgrades).
  lachain-tpu fsck --config netdir/config0.json [--deep] [--no-repair]
      storage invariant scan: detects torn states (orphan block, lost
      state roots, stale journal eras), repairs what is safely repairable.
      Exit 0 = clean or repaired; 1 = refused (operator runbook in
      DEPLOY.md); 2 = no database.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import secrets
import signal
import sys
from typing import List

logger = logging.getLogger("lachain_tpu.cli")


# ---------------------------------------------------------------------------
# keygen
# ---------------------------------------------------------------------------


def cmd_keygen(args) -> int:
    from .consensus.keys import trusted_key_gen
    from .core.config import CURRENT_VERSION
    from .core.vault import PrivateWallet
    from .crypto import ecdsa

    n, f = args.n, args.f
    if n <= 3 * f:
        print(f"need n > 3f (got n={n}, f={f})", file=sys.stderr)
        return 2
    os.makedirs(args.out, exist_ok=True)
    pub, privs = trusted_key_gen(n, f)
    peers: List[str] = []
    for i in range(n):
        port = args.port_base + 2 * i
        peers.append(
            f"{args.host}:{port}:{pub.ecdsa_pub_keys[i].hex()}"
        )
    balances = {}
    for i in range(n):
        addr = ecdsa.address_from_public_key(pub.ecdsa_pub_keys[i])
        balances["0x" + addr.hex()] = str(args.initial_balance)
    for extra in args.fund or []:
        balances[extra] = str(args.initial_balance)
    consensus_hex = pub.encode().hex()
    regions = (
        [r.strip() for r in args.regions.split(",") if r.strip()]
        if getattr(args, "regions", None)
        else []
    )
    for i in range(n):
        wallet_path = os.path.join(args.out, f"wallet{i}.json")
        password = secrets.token_hex(8) if args.encrypt else ""
        wallet = PrivateWallet(
            path=wallet_path,
            password=password,
            ecdsa_priv=privs[i].ecdsa_priv,
        )
        wallet.add_threshold_keys(0, privs[i].tpke_priv, privs[i].ts_share)
        wallet.save()
        if password:
            # never written to the config: hand it to the operator once;
            # `run` reads LACHAIN_WALLET_PASSWORD at startup
            print(f"wallet{i} password: {password}", file=sys.stderr)
        cfg = {
            "version": CURRENT_VERSION,
            "network": {
                "host": args.host,
                "port": args.port_base + 2 * i,
                "peers": [p for j, p in enumerate(peers) if j != i],
            },
            "genesis": {
                "chainId": args.chain_id,
                "balances": balances,
                "consensusKeys": consensus_hex,
                "validatorIndex": i,
            },
            "vault": {"path": wallet_path, "password": ""},
            "staking": {
                "cycleDuration": args.cycle_duration,
                "vrfSubmissionPhase": args.vrf_phase,
                "attendanceDetectionDuration": max(
                    min(100, args.cycle_duration // 5), 1
                ),
            },
            "rpc": {
                "enabled": True,
                "host": "127.0.0.1",
                "port": args.port_base + 2 * i + 1,
                "apiKey": None,
            },
            "blockchain": {"targetTxsPerBlock": 1000, "targetBlockTimeMs": args.block_time_ms},
            # fresh chains activate every current hardfork from genesis —
            # written EXPLICITLY so the chain's schedule never depends on
            # library defaults (migrated configs get the NEVER sentinel
            # instead, core/config.py _v5_to_v6)
            "hardfork": {"heights": {"fast_wasm_gas": 0}},
            # written explicitly for the same reason: the engine a chain's
            # database is created with is permanent (migrated <=v6 configs
            # get sqlite pinned instead, core/config.py _v6_to_v7)
            "storage": {"engine": "lsm"},
        }
        # WAN emulation knobs are additive network keys: `region` labels the
        # node's emulated region (round-robin over --regions, matching
        # LinkShaper's positional striping), `wanShaper` carries the shared
        # LinkShaper spec so the emulated matrix is fleet-wide consistent
        if regions:
            cfg["network"]["region"] = regions[i % len(regions)]
        if getattr(args, "wan", None):
            cfg["network"]["wanShaper"] = args.wan
        path = os.path.join(args.out, f"config{i}.json")
        with open(path, "w") as fh:
            json.dump(cfg, fh, indent=2, sort_keys=True)
        print(path)
    return 0


# ---------------------------------------------------------------------------
# run
# ---------------------------------------------------------------------------


def _build_node(cfg, config_path=None):
    from .consensus.keys import PrivateConsensusKeys, PublicConsensusKeys
    from .core import system_contracts as sc
    from .core.hardforks import set_hardfork_heights
    from .core.node import Node
    from .core.vault import PrivateWallet
    from .network.hub import PeerAddress
    from .storage.kv import SqliteKV
    from .storage.lsm import LsmKV

    sc.set_cycle_params(
        cfg.staking.cycle_duration,
        cfg.staking.vrf_submission_phase,
        cfg.staking.attendance_detection_duration,
    )
    if cfg.hardfork.heights:
        set_hardfork_heights(cfg.hardfork.heights, force=True)
    if cfg.ignored_keys:
        logger.warning(
            "config keys %s have no effect: blocks execute and freeze on "
            "one path",
            ", ".join(cfg.ignored_keys),
        )
    if cfg.trace_capacity is not None:
        # resize the merged rings now; native engines created after this
        # point (LSM store below, consensus engine per era) size their
        # in-engine rings from the same knob via tracing.capacity().
        # 0 turns the whole recorder off
        from .utils import tracing

        tracing.set_capacity(cfg.trace_capacity)
    if cfg.tx_sample_shift is not None:
        # tx lifecycle sampling density: 1-in-2^shift transactions carry
        # stage stamps (observability.txSampleShift; 0 = stamp every tx)
        from .utils import txtrace

        txtrace.set_sample_shift(int(cfg.tx_sample_shift))
    password = cfg.vault.password or os.environ.get(
        "LACHAIN_WALLET_PASSWORD", ""
    )
    wallet = PrivateWallet.load(cfg.vault.path, password)
    pub = PublicConsensusKeys.decode(bytes.fromhex(cfg.genesis.consensus_keys))
    idx = cfg.genesis.validator_index
    priv = wallet.consensus_keys_for_era(0)
    if priv is None or idx < 0:
        priv = PrivateConsensusKeys.observer(wallet.ecdsa_priv)
        idx = -1
    balances = {
        bytes.fromhex(a[2:]): int(v) for a, v in cfg.genesis.balances.items()
    }
    db_path = cfg.storage_path
    if db_path is None and config_path is not None:
        db_path = os.path.splitext(config_path)[0] + ".db"
    node = Node(
        index=idx,
        public_keys=pub,
        private_keys=priv,
        chain_id=cfg.genesis.chain_id,
        kv=(
            (LsmKV if cfg.storage_engine == "lsm" else SqliteKV)(db_path)
            if db_path
            else None
        ),
        host=cfg.network.host,
        port=cfg.network.port,
        advertise_host=cfg.network.advertise_host,
        relay=cfg.network.relay,
        initial_balances=balances,
        txs_per_block=cfg.blockchain.target_txs_per_block,
        wallet=wallet,
        block_interval=cfg.blockchain.target_block_time_ms / 1000.0,
        pipeline_window=cfg.blockchain.pipeline_window,
    )
    if cfg.idle_alert_fraction is not None:
        # observability.idleAlertFraction: /healthz reads degraded when
        # the rolling era idle fraction exceeds this
        node.idle_alert_fraction = float(cfg.idle_alert_fraction)
    if cfg.wan_shaper:
        # network.wanShaper: emulated WAN matrix on this node's outbound
        # frames (network/faults.py LinkShaper), seeded with the chain id
        node.network.install_wan_shaper(
            cfg.wan_shaper, idx, pub.ecdsa_pub_keys, cfg.genesis.chain_id
        )
    peers = []
    for spec in cfg.network.peers:
        host, port, pubhex = spec.rsplit(":", 2)
        peers.append(
            PeerAddress(
                public_key=bytes.fromhex(pubhex), host=host, port=int(port)
            )
        )
    return node, peers


async def _run_node(cfg, args) -> None:
    node, peers = _build_node(cfg, args.config)
    want_fast = bool(getattr(args, "fast_sync", False)) and peers
    await node.start(start_synchronizer=not want_fast)
    node.connect(peers)
    if want_fast:
        # reference Application.Start: FastSynchronizerBatch BEFORE the
        # block synchronizer, so replay doesn't race the state download
        await asyncio.sleep(1.0)  # let peer connections establish
        checkpoint = getattr(args, "trusted_checkpoint", None)
        if checkpoint:
            height_s, hash_s = checkpoint.split(":", 1)
            node.fast_sync.trusted = (
                int(height_s),
                bytes.fromhex(hash_s.removeprefix("0x")),
            )
        try:
            # all configured peers form the serving set: the scheduler
            # spreads batches across them and fails over on its own
            h = await node.fast_sync.sync(
                [peer.public_key for peer in peers],
                timeout=120,
                snapshot=bool(getattr(args, "snapshot", False)),
            )
            print(f"fast-synced to height {h}", flush=True)
        except Exception as e:
            logger.warning("fast sync failed: %s", e)
        node.start_services()
    rpc = None
    if cfg.rpc.enabled:
        rpc = await node.start_rpc(
            cfg.rpc.host,
            cfg.rpc.port,
            api_key=cfg.rpc.api_key,
            auth_pubkey=cfg.rpc.auth_pubkey,
        )
        print(f"rpc: http://{cfg.rpc.host}:{rpc.port}", flush=True)
    if args.stake:
        node.validator_status.become_staker(int(args.stake))

    stop = asyncio.Event()

    def _sig(*_a):
        stop.set()

    loop = asyncio.get_running_loop()
    for s in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(s, _sig)
        except NotImplementedError:
            pass

    run_task = asyncio.ensure_future(
        node.run(first_era=node.block_manager.current_height() + 1)
    )
    stop_task = asyncio.ensure_future(stop.wait())
    await asyncio.wait(
        [run_task, stop_task], return_when=asyncio.FIRST_COMPLETED
    )
    failure = None
    if run_task.done() and not run_task.cancelled():
        failure = run_task.exception()
    run_task.cancel()
    stop_task.cancel()
    await node.stop()
    if failure is not None:
        # surface the lifecycle crash: the process must exit non-zero so
        # supervisors restart it, not report success
        raise failure


CONSOLE_COMMANDS = """\
Commands:
  height                       chain tip
  block <number|latest>        block summary
  tx <hash>                    transaction
  receipt <hash>               execution receipt
  balance <0xaddr>             account balance
  nonce <0xaddr>               account nonce
  account                      the node wallet's account
  peers                        connected peer pubkeys
  validators                   current validator set
  consensus                    era/N/F/keys summary
  pool                         pending tx hashes
  phase                        cycle phase (vrf/attendance windows)
  penalty <0xaddr>             accrued attendance penalty
  metrics                      node timer/counter snapshot
  unlock <password> [seconds]  unlock the node wallet
  lock?                        wallet lock status
  send <0xto> <value>          transfer from the node wallet
  sendraw <0xhex>              submit a raw signed tx
  stake <amount>               stake from the node balance
  unstake                      request stake withdrawal
  help                         this text
  exit                         leave the console
"""


def _console_eval(call, line: str) -> object:
    """One console command -> RPC call(s). `call(method, *params)`."""
    parts = line.split()
    if not parts:
        return None
    cmd, args = parts[0].lower(), parts[1:]
    if cmd in ("help", "?"):
        return CONSOLE_COMMANDS
    if cmd == "height":
        return int(call("eth_blockNumber"), 16)
    if cmd == "block":
        tag = args[0] if args else "latest"
        if tag.isdigit():
            tag = hex(int(tag))
        return call("eth_getBlockByNumber", tag, False)
    if cmd == "tx":
        return call("eth_getTransactionByHash", args[0])
    if cmd == "receipt":
        return call("eth_getTransactionReceipt", args[0])
    if cmd == "balance":
        return int(call("eth_getBalance", args[0]), 16)
    if cmd == "nonce":
        return int(call("eth_getTransactionCount", args[0]), 16)
    if cmd == "account":
        return call("fe_account")
    if cmd == "peers":
        return call("net_peers")
    if cmd == "validators":
        return call("la_getLatestValidators")
    if cmd == "consensus":
        return call("la_consensusState")
    if cmd == "pool":
        return call("eth_getTransactionPool")
    if cmd == "phase":
        return call("fe_phase")
    if cmd == "penalty":
        addr = args[0] if args else None
        out = {"penalty": int(call("la_getPenalty", *( [addr] if addr else [] )), 16)}
        if addr:
            out.update(call("la_validatorInfo", addr))
        return out
    if cmd == "metrics":
        return call("la_metrics")
    if cmd == "unlock":
        secs = hex(int(args[1])) if len(args) > 1 else "0x12c"
        return call("fe_unlock", args[0], secs)
    if cmd == "lock?":
        return {"locked": call("fe_isLocked")}
    if cmd == "send":
        return call(
            "eth_sendTransaction", {"to": args[0], "value": hex(int(args[1]))}
        )
    if cmd == "sendraw":
        return call("eth_sendRawTransaction", args[0])
    if cmd == "stake":
        return call("validator_start_with_stake", hex(int(args[0])))
    if cmd == "unstake":
        return call("validator_stop")
    raise ValueError(f"unknown command {cmd!r} (try 'help')")


def cmd_console(args) -> int:
    import urllib.request

    def call(method, *params):
        body = json.dumps(
            {"jsonrpc": "2.0", "id": 1, "method": method, "params": list(params)}
        ).encode()
        req = urllib.request.Request(
            args.rpc, data=body, headers={"Content-Type": "application/json"}
        )
        with urllib.request.urlopen(req, timeout=args.timeout) as resp:
            out = json.loads(resp.read())
        if "error" in out:
            raise RuntimeError(out["error"].get("message", out["error"]))
        return out["result"]

    failures = [0]

    def run_line(line) -> bool:
        line = line.strip()
        if line in ("exit", "quit"):
            return False
        if not line:
            return True
        try:
            out = _console_eval(call, line)
            if isinstance(out, str):
                print(out)
            else:
                print(json.dumps(out, indent=2, sort_keys=True))
        except Exception as exc:  # operator tool: report, keep the shell
            failures[0] += 1
            print(f"error: {exc}", file=sys.stderr)
        return True

    if args.exec:
        for line in args.exec.split(";"):
            if not run_line(line):
                break
        # scriptable mode: a failed command must fail the invocation so
        # shell `&&` chains can react, unlike the keep-going interactive loop
        return 1 if failures[0] else 0
    try:
        import readline  # noqa: F401  (history/arrow keys when available)
    except ImportError:
        pass
    print(f"lachain-tpu console — attached to {args.rpc} ('help' for commands)")
    while True:
        try:
            line = input("lachain> ")
        except (EOFError, KeyboardInterrupt):
            print()
            return 0
        try:
            if not run_line(line):
                return 0
        except KeyboardInterrupt:
            # ^C mid-command aborts the command, not the shell
            print("\ninterrupted", file=sys.stderr)


def cmd_trace(args) -> int:
    """Pull the node's era-lifecycle trace over RPC. Default output is
    Chrome trace_event JSON — load it in chrome://tracing or Perfetto."""
    import urllib.request

    if args.era_report or args.critical_path:
        method = "la_getEraReport"
    elif args.summary:
        method = "la_getTraceSummary"
    else:
        method = "la_getTrace"
    params = (
        []
        if args.summary or args.era_report or args.critical_path
        or args.limit is None
        else [args.limit]
    )
    body = json.dumps(
        {"jsonrpc": "2.0", "id": 1, "method": method, "params": params}
    ).encode()
    req = urllib.request.Request(
        args.rpc, data=body, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=args.timeout) as resp:
        out = json.loads(resp.read())
    if "error" in out:
        print(f"error: {out['error'].get('message', out['error'])}",
              file=sys.stderr)
        return 1
    result = out["result"]
    if args.era_report or args.critical_path:
        from .utils import tracing

        if args.era_report:
            print(tracing.era_report_table(result))
        if args.critical_path:
            print(tracing.critical_path_table(result))
        reported = result.get("eras", [])
        if reported and args.out:
            with open(args.out, "w") as fh:
                fh.write(json.dumps(result, indent=2))
            print(f"era report -> {args.out}")
        return 0
    if args.summary:
        print(json.dumps(result, indent=2, sort_keys=True))
        return 0
    text = json.dumps(result)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text)
        print(
            f"{len(result.get('traceEvents', []))} events -> {args.out} "
            "(open in chrome://tracing or https://ui.perfetto.dev)"
        )
    else:
        print(text)
    return 0


def cmd_fleet_trace(args) -> int:
    """Scrape N nodes' traces/era reports/health over RPC, align their
    clocks by RTT-bracketed la_time pings, and write ONE merged Chrome
    trace with a pid lane block per node. Searching the merged trace for
    a sampled tx's 16-hex-char trace id (la_getTxTrace -> traceId) lights
    up its lifecycle across every node that touched it."""
    from .utils import fleetview

    names = args.names.split(",") if args.names else None
    if names is not None and len(names) != len(args.rpc):
        print("error: --names count must match --rpc count", file=sys.stderr)
        return 1
    merged, report = fleetview.collect(
        args.rpc,
        names=names,
        samples=args.samples,
        timeout=args.timeout,
        api_key=args.api_key,
    )
    unreachable = [
        n["name"]
        for n in merged["fleet"]["nodes"]
        if n["errors"].get("trace") and n["errors"].get("eraReport")
    ]
    if unreachable:
        print(
            f"warning: no data from {', '.join(unreachable)}",
            file=sys.stderr,
        )
        if len(unreachable) == len(args.rpc):
            print("error: every node unreachable", file=sys.stderr)
            return 1
    print(fleetview.fleet_era_table(report))
    for n in merged["fleet"]["nodes"]:
        status = n["status"] or "?"
        unc = n["uncertaintyUs"]
        rtt = n.get("rttMaxMs")
        wirev = n.get("wireVersion")
        print(
            f"{n['name']}: status={status} "
            f"offset={n['offsetUs'] or 0:.0f}us"
            + (f" (±{unc:.0f}us)" if unc is not None else "")
            + (f" rtt_max={rtt:.0f}ms" if rtt is not None else "")
            + (f" wire=v{wirev}" if wirev is not None else "")
        )
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(json.dumps(merged))
        n_events = sum(
            1 for e in merged["traceEvents"] if e.get("ph") != "M"
        )
        print(
            f"{n_events} events from {len(args.rpc)} nodes -> {args.out} "
            "(open in chrome://tracing or https://ui.perfetto.dev)"
        )
    return 0


def cmd_chaos(args) -> int:
    """Seeded fault-injection run: an in-process devnet pushed through
    `--eras` eras under a FaultPlan, printing an era/recovery report.
    Exit 0 iff every era decided identically on every node."""
    import time

    from .core.devnet import Devnet
    from .network.faults import FaultPlan
    from .utils import metrics

    if args.crash_point:
        # storage crash scenario: orthogonal to the network fault plan (a
        # SIGKILLed child + fsck + resume, not an in-process devnet)
        return _run_crash_point_scenario(args)
    adversary = None
    if args.byzantine:
        from .consensus.adversary import AdversaryPlan

        traitors = (
            tuple(int(t) for t in args.traitors.split(","))
            if args.traitors
            else tuple(range(args.f))
        )
        try:
            adversary = AdversaryPlan(
                strategy=args.byzantine, traitors=traitors, seed=args.seed
            )
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    shaper = None
    if getattr(args, "wan", None):
        from .network.faults import LinkShaper

        try:
            shaper = LinkShaper.parse(args.wan)
        except ValueError as e:
            print(f"error: bad --wan spec: {e}", file=sys.stderr)
            return 2
    plan = FaultPlan(
        seed=args.seed,
        drop=args.drop,
        duplicate=args.duplicate,
        delay=args.delay,
        reorder=args.reorder,
        crashes=tuple(FaultPlan.parse_crash(s) for s in args.crash),
        partitions=tuple(
            FaultPlan.parse_partition(s) for s in args.partition
        ),
        shaper=shaper,
    )
    print(
        f"chaos: n={args.n} f={args.f} eras={args.eras} seed={args.seed} "
        f"engine={args.engine}"
    )
    print(
        f"plan: drop={plan.drop} duplicate={plan.duplicate} "
        f"delay={plan.delay} reorder={plan.reorder} "
        f"crashes={len(plan.crashes)} partitions={len(plan.partitions)}"
        + (f" wan={args.wan}" if shaper is not None else "")
    )
    if adversary is not None:
        print(
            f"byzantine: strategy={adversary.strategy} "
            f"traitors={list(adversary.traitors)} seed={adversary.seed}"
        )
    try:
        net = Devnet(
            n=args.n,
            f=args.f,
            seed=args.seed,
            fault_plan=plan,
            engine=args.engine,
            adversary=adversary,
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    failures = 0
    for era in range(1, args.eras + 1):
        t0 = time.perf_counter()
        delivered0 = net.net.delivered_count
        recov0 = getattr(net.net, "recovery_rounds", 0)
        try:
            blocks = net.run_era(era)
        except RuntimeError as e:
            failures += 1
            print(f"era {era:>3}: FAILED ({e})")
            continue
        dt = time.perf_counter() - t0
        era_ev = ""
        if adversary is not None:
            from .consensus.evidence import era_counts

            counts = era_counts(era)
            era_ev = (
                f" equivocations={counts.get('equivocation', 0)}"
                f" invalid_shares={counts.get('invalid_share', 0)}"
            )
        print(
            f"era {era:>3}: block {blocks[0].hash().hex()[:16]} "
            f"msgs={net.net.delivered_count - delivered0} "
            f"recovery_rounds={getattr(net.net, 'recovery_rounds', 0) - recov0} "
            f"{dt:.2f}s{era_ev}"
        )
    faults = getattr(net.net, "faults", None)
    if faults is not None:
        print("fault report:", json.dumps(faults.stats, sort_keys=True))
    replayed = metrics.counter_value("consensus_outbox_replayed_total")
    evicted = metrics.counter_value("consensus_outbox_evicted_total")
    print(
        f"recovery report: recovery_rounds="
        f"{getattr(net.net, 'recovery_rounds', 0)} "
        f"outbox_replayed={int(replayed)} outbox_evicted={int(evicted)}"
    )
    if adversary is not None:
        # evidence identity: honest nodes must have detected the SAME set
        honest = [
            i for i in range(args.n) if i not in adversary.traitors
        ]
        sets = [net.net.routers[i].evidence.record_set() for i in honest]
        shed = metrics.counter_value(
            "consensus_msgs_shed_total", labels={"reason": "latch_cap"}
        )
        print(
            f"byzantine report: evidence_records={len(sets[0])} "
            f"evidence_identical={all(s == sets[0] for s in sets)} "
            f"latch_shed={int(shed)}"
        )
        for rec in net.net.routers[honest[0]].evidence.snapshot():
            print(f"  evidence: {json.dumps(rec, sort_keys=True)}")
    heights = [net.height(i) for i in range(args.n)]
    print(f"heights: {heights}")
    if failures or len(set(heights)) != 1:
        print("CHAOS RUN FAILED", file=sys.stderr)
        return 1
    print(f"ok: {args.eras} eras survived the plan")
    return 0


def cmd_fleet_upgrade(args) -> int:
    """Zero-downtime rolling-upgrade drill: boot an n-node loopback TCP
    fleet on the legacy (pre-handshake) wire, optionally WAN-shaped into
    emulated regions, then roll every node one at a time onto the
    upgraded wire while the survivors keep committing eras under paced
    open-loop traffic. Gates: /healthz stays `ok` on every live node at
    every era checkpoint and the FLEET misses zero eras (a rolling node
    sitting one out is the expected shape). Prints one
    JSON result line (era_latency_p99_s + rtt_ms)."""
    import random
    import time

    from .core.fleet import TcpFleet
    from .core.types import Transaction, sign_transaction
    from .crypto import ecdsa
    from .network import wire
    from .network.faults import LinkShaper

    shaper = None
    if args.wan:
        try:
            shaper = LinkShaper.parse(args.wan)
        except ValueError as e:
            print(f"error: bad --wan spec: {e}", file=sys.stderr)
            return 2

    class _Rng:
        def __init__(self, seed):
            self._r = random.Random(seed)

        def randbelow(self, k):
            return self._r.randrange(k)

    async def drill() -> int:
        user_priv = ecdsa.generate_private_key(_Rng(args.seed + 1))
        user_addr = ecdsa.address_from_public_key(
            ecdsa.public_key_bytes(user_priv)
        )
        fleet = TcpFleet(
            n=args.n,
            f=args.f,
            seed=args.seed,
            txs_per_block=max(128, args.txs_per_era),
            initial_balances={user_addr: 10**24},
            shaper=shaper,
            legacy_wire=True,
            era_timeout=args.era_timeout,
        )
        era = 0
        nonce = 0
        era_lat: List[float] = []
        failures: List[str] = []

        async def one_era() -> None:
            nonlocal era, nonce
            era += 1
            txs = [
                sign_transaction(
                    Transaction(
                        to=b"\x0d" * 20,
                        value=1,
                        nonce=nonce + j,
                        gas_price=1,
                        gas_limit=21000,
                    ),
                    user_priv,
                    fleet.chain_id,
                )
                for j in range(args.txs_per_era)
            ]
            nonce += args.txs_per_era
            await fleet.submit_and_settle(txs)
            t0 = time.perf_counter()
            h = await fleet.run_era(era)
            era_lat.append(time.perf_counter() - t0)
            bad = {
                i: s for i, s in fleet.health_statuses().items() if s != "ok"
            }
            if bad:
                failures.append(f"era {era}: health left ok: {bad}")
            print(
                f"era {era:>3}: {h.hex()[:16]} {era_lat[-1]:.2f}s "
                f"health={'ok' if not bad else bad} rtt_ms={fleet.rtt_ms()}"
            )

        await fleet.start()
        try:
            for _ in range(args.warmup):
                await one_era()
            for i in range(args.n):
                await fleet.take_down(i)
                region = fleet.region_of(i) or "-"
                print(f"roll: node {i} down (region {region})")
                await one_era()  # survivors commit with node i out
                await fleet.bring_up(i, next_era=era + 1)
                print(
                    f"roll: node {i} back on wire v{fleet.wire_versions()[i]}"
                )
            for _ in range(args.cooldown):
                await one_era()
            rtt = fleet.rtt_ms()
            versions = fleet.wire_versions()
        finally:
            await fleet.stop()
        if any(v != wire.WIRE_VERSION for v in versions.values()):
            failures.append(f"nodes left on the old wire: {versions}")
        lat = sorted(era_lat)
        result = {
            "metric": "fleet_upgrade",
            "n": args.n,
            "f": args.f,
            "eras": era,
            "rolled": args.n,
            "era_latency_p50_s": round(lat[len(lat) // 2], 4),
            "era_latency_p99_s": round(
                lat[min(len(lat) - 1, int(0.99 * len(lat)))], 4
            ),
            "rtt_ms": rtt,
            "wire_versions": {str(k): v for k, v in versions.items()},
            "healthz_ok": not failures,
            "wan": args.wan or "",
        }
        print(json.dumps(result, sort_keys=True))
        if failures:
            for msg in failures:
                print(msg, file=sys.stderr)
            print("FLEET-UPGRADE DRILL FAILED", file=sys.stderr)
            return 1
        print(
            f"ok: rolled {args.n}/{args.n} nodes, zero fleet missed eras, "
            f"/healthz ok throughout"
        )
        return 0

    return asyncio.run(drill())


def cmd_run(args) -> int:
    from .core.config import NodeConfig

    logging.basicConfig(
        level=os.environ.get("LOG_LEVEL", "INFO"),
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    cfg = NodeConfig.load(args.config)
    try:
        asyncio.run(_run_node(cfg, args))
    except KeyboardInterrupt:
        pass
    return 0


def cmd_height(args) -> int:
    from .core.config import NodeConfig

    cfg = NodeConfig.load(args.config)
    node, _ = _build_node(cfg, args.config)
    print(
        json.dumps(
            {
                "height": node.block_manager.current_height(),
                # the committed state root: the --expect-root value for
                # db import and the operator's cross-node consistency check
                "stateHash": node.state.committed.state_hash().hex(),
                "chainId": node.chain_id,
                "validators": node.public_keys.n,
            }
        )
    )
    return 0


_DB_DUMP_MAGIC = b"LKVD0001"


def cmd_db(args) -> int:
    """Offline database maintenance: shrink (prune old trie checkpoints),
    rollback (restore an older snapshot) — reference `lachain db` verbs
    + --RollBackTo (Program.cs:25-39, Application.cs:119-127) — plus
    compact (LSM full merge), and export/import (engine-portable dump;
    the supported migration path between storage engines, since sqlite and
    LSM on-disk formats are not interchangeable). The node must be
    STOPPED: these operations mutate or snapshot the store
    non-transactionally with respect to concurrent commits."""
    from .core.config import NodeConfig
    from .storage.kv import SqliteKV
    from .storage.lsm import LsmKV
    from .storage.shrink import DbShrink
    from .storage.state import StateManager

    cfg = NodeConfig.load(args.config)
    db_path = cfg.storage_path or (
        os.path.splitext(args.config)[0] + ".db"
    )
    make_kv = LsmKV if cfg.storage_engine == "lsm" else SqliteKV

    if args.db_cmd == "import":
        # target must be FRESH: importing over live state would interleave
        # two chains' keys into one store
        if os.path.exists(db_path):
            print(f"refusing import: {db_path} already exists", file=sys.stderr)
            return 1
        count = 0
        kv = make_kv(db_path)
        try:
            with open(args.dump, "rb") as fh:
                if fh.read(len(_DB_DUMP_MAGIC)) != _DB_DUMP_MAGIC:
                    print(f"{args.dump}: not a db export", file=sys.stderr)
                    return 1
                batch = []
                while True:
                    head = fh.read(4)
                    if not head:
                        break
                    klen = int.from_bytes(head, "little")
                    k = fh.read(klen)
                    vlen = int.from_bytes(fh.read(4), "little")
                    v = fh.read(vlen)
                    if len(k) != klen or len(v) != vlen:
                        print(f"{args.dump}: truncated", file=sys.stderr)
                        return 1
                    batch.append((k, v))
                    count += 1
                    if len(batch) >= 2000:
                        kv.write_batch(batch)
                        batch = []
                if batch:
                    kv.write_batch(batch)
            # migration/snapshot contract: a dump is not self-certifying.
            # The imported tip's state roots must hash to the operator-
            # supplied --expect-root (read from a trusted block header);
            # without the flag a non-empty import is refused outright.
            from .storage.fsck import verify_imported_state

            expect = getattr(args, "expect_root", None)
            expect_hash = (
                bytes.fromhex(expect.removeprefix("0x")) if expect else None
            )
            problem = (
                verify_imported_state(kv, expect_hash) if count else None
            )
        finally:
            kv.close()
        if problem is not None:
            # remove the refused store so a corrected re-run is not
            # blocked by the freshness check above
            import shutil

            if os.path.isdir(db_path):
                shutil.rmtree(db_path, ignore_errors=True)
            elif os.path.exists(db_path):
                os.remove(db_path)
            print(f"import verification failed: {problem}", file=sys.stderr)
            return 1
        print(json.dumps({"imported": count, "engine": cfg.storage_engine,
                          "verifiedRoot": expect or None}))
        return 0

    if not os.path.exists(db_path):
        print(f"no database at {db_path}", file=sys.stderr)
        return 1
    # same engine switch as the node itself: maintenance verbs must open
    # the store the node actually wrote
    kv = make_kv(db_path)
    try:
        if args.db_cmd == "shrink":
            state = StateManager(kv)
            stats = DbShrink(state, kv).shrink(args.retain)
            print(json.dumps(stats))
        elif args.db_cmd == "rollback":
            state = StateManager(kv)
            height = args.height
            old = state.committed_height()
            try:
                state.rollback_to(height)
            except KeyError as e:
                print(str(e), file=sys.stderr)
                return 1
            print(
                json.dumps({"rolledBackFrom": old, "height": height})
            )
        elif args.db_cmd == "compact":
            if not isinstance(kv, LsmKV):
                print("compact: only the lsm engine", file=sys.stderr)
                return 1
            before = kv.table_count()
            kv.compact()
            print(json.dumps(
                {"tablesBefore": before, "tablesAfter": kv.table_count(),
                 "stats": kv.stats()}
            ))
        elif args.db_cmd == "export":
            count = 0
            with open(args.out, "wb") as fh:
                fh.write(_DB_DUMP_MAGIC)
                for k, v in kv.scan_prefix(b""):
                    fh.write(len(k).to_bytes(4, "little") + k)
                    fh.write(len(v).to_bytes(4, "little") + v)
                    count += 1
            print(json.dumps({"exported": count, "path": args.out}))
    finally:
        kv.close()
    return 0


def cmd_fsck(args) -> int:
    """Storage invariant scan (storage/fsck.py): the standalone verb for
    what the node runs on every open. Exit codes: 0 clean-or-repaired,
    1 refused (fatal issues — see the DEPLOY.md runbook), 2 no database."""
    from .core.config import NodeConfig
    from .storage.fsck import fsck
    from .storage.kv import SqliteKV
    from .storage.lsm import LsmKV

    cfg = NodeConfig.load(args.config)
    db_path = cfg.storage_path or (
        os.path.splitext(args.config)[0] + ".db"
    )
    if not os.path.exists(db_path):
        print(f"no database at {db_path}", file=sys.stderr)
        return 2
    kv = (LsmKV if cfg.storage_engine == "lsm" else SqliteKV)(db_path)
    try:
        report = fsck(kv, repair=not args.no_repair, deep=args.deep)
    finally:
        kv.close()
    print(json.dumps(report.to_dict(), indent=2, sort_keys=True))
    return 1 if report.fatal else 0


def _run_crash_point_scenario(args) -> int:
    """chaos --crash-point: SIGKILL a real child process at a named storage
    pipeline point, then prove the recovery story — fsck detects/repairs
    the torn state and a resumed run completes. Repeating the same spec is
    deterministic: the report prints the final chain height both times."""
    import subprocess
    import tempfile

    from .storage import crash_workload, crashpoints
    from .storage.fsck import fsck

    specs = []
    for spec in args.crash_point:
        point = crashpoints.CrashPlan.parse_point(spec)
        # the child must genuinely die: force sigkill mode
        specs.append(
            crashpoints.CrashPoint(
                name=point.name, hit=point.hit, mode=crashpoints.MODE_SIGKILL
            )
        )
    plan = crashpoints.CrashPlan(points=tuple(specs))
    print(f"chaos crash-point: plan={plan.encode_env()} engine={args.engine}")
    failures = 0
    with tempfile.TemporaryDirectory() as tmp:
        db_path = os.path.join(tmp, "chaos.db")
        env = dict(os.environ)
        env[crashpoints.ENV_VAR] = plan.encode_env()
        env.setdefault("JAX_PLATFORMS", "cpu")
        child = subprocess.run(
            [
                sys.executable,
                "-m",
                "lachain_tpu.storage.crash_workload",
                db_path,
                args.engine,
            ],
            env=env,
            capture_output=True,
            text=True,
        )
        killed = child.returncode == -signal.SIGKILL
        print(
            f"child: rc={child.returncode} "
            f"({'SIGKILLed at plan point' if killed else 'ran to completion'})"
        )
        if not killed:
            print(
                "crash point never fired — the workload does not traverse "
                f"{[p.name for p in plan.points]}",
                file=sys.stderr,
            )
            return 1
        kv = crash_workload.open_kv(db_path, args.engine)
        try:
            report = fsck(kv, repair=True)
            print("fsck:", json.dumps(report.to_dict(), sort_keys=True))
            if report.fatal:
                failures += 1
            recheck = fsck(kv, repair=False)
            if recheck.fatal:
                print("fsck recheck still fatal after repair", file=sys.stderr)
                failures += 1
            # resume: the workload continues from the committed tip
            stats = crash_workload.run_workload(kv)
            print("resumed run:", json.dumps(stats, sort_keys=True))
            if stats["height"] != crash_workload.DEFAULT_BLOCKS:
                failures += 1
        finally:
            kv.close()
    if failures:
        print("CHAOS CRASH-POINT RUN FAILED", file=sys.stderr)
        return 1
    print("ok: crashed, repaired, resumed")
    return 0


def cmd_encrypt(args) -> int:
    """Password-protect (or re-key) a wallet file in place
    (reference `lachain encrypt`, Program.cs:25-39)."""
    from .core.vault import PrivateWallet

    old_pw = args.old_password or os.environ.get(
        "LACHAIN_WALLET_PASSWORD", ""
    )
    wallet = PrivateWallet.load(args.wallet, old_pw)
    wallet.set_password(args.password)
    wallet.save(args.wallet)
    print(json.dumps({"wallet": args.wallet, "encrypted": bool(args.password)}))
    return 0


def cmd_decrypt(args) -> int:
    """Print a wallet's decrypted JSON to stdout (reference
    `lachain decrypt`) — for operator inspection/backup; keys go to the
    terminal, so use deliberately."""
    from .core.vault import PrivateWallet

    pw = args.password or os.environ.get("LACHAIN_WALLET_PASSWORD", "")
    wallet = PrivateWallet.load(args.wallet, pw)
    print(wallet.to_json())
    return 0


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="lachain-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    kg = sub.add_parser("keygen", help="generate a trusted-dealer devnet")
    kg.add_argument("--n", type=int, required=True)
    kg.add_argument("--f", type=int, required=True)
    kg.add_argument("--out", required=True)
    kg.add_argument("--host", default="127.0.0.1")
    kg.add_argument("--port-base", type=int, default=7070)
    kg.add_argument("--chain-id", type=int, default=225)
    kg.add_argument("--cycle-duration", type=int, default=1000)
    kg.add_argument("--vrf-phase", type=int, default=500)
    kg.add_argument("--initial-balance", type=int, default=10**24)
    kg.add_argument("--block-time-ms", type=int, default=1000)
    kg.add_argument(
        "--fund", nargs="*", help="extra 0x addresses to fund at genesis"
    )
    kg.add_argument(
        "--encrypt", action="store_true", help="password-protect wallets"
    )
    kg.add_argument(
        "--regions",
        metavar="R1,R2,...",
        help="stripe nodes round-robin across these emulated regions "
             "(written as network.region; node i gets region i %% len)",
    )
    kg.add_argument(
        "--wan",
        metavar="SPEC",
        help="LinkShaper spec written to every config's network.wanShaper, "
             "e.g. 'regions=us,eu,ap,sa;default=80ms/8ms@4mbps;intra=2ms'",
    )
    kg.set_defaults(fn=cmd_keygen)

    rn = sub.add_parser("run", help="run a node from a config")
    rn.add_argument("--config", required=True)
    rn.add_argument("--stake", help="stake this amount at startup")
    rn.add_argument(
        "--fast-sync",
        action="store_true",
        help="download state from the configured peers instead of "
        "replaying blocks (multi-peer, with failover)",
    )
    rn.add_argument(
        "--snapshot",
        action="store_true",
        help="with --fast-sync: bulk-import a snapshot stream first, "
        "then trie-walk only the diff",
    )
    rn.add_argument(
        "--trusted-checkpoint",
        metavar="HEIGHT:BLOCKHASH",
        help="with --fast-sync: accept the target block by this "
        "checkpoint instead of a genesis-validator multisig quorum "
        "(required once the chain has rotated validators)",
    )
    rn.set_defaults(fn=cmd_run)

    ht = sub.add_parser("height", help="print local chain status")
    ht.add_argument("--config", required=True)
    ht.set_defaults(fn=cmd_height)

    db = sub.add_parser("db", help="offline database maintenance")
    dbsub = db.add_subparsers(dest="db_cmd", required=True)
    sh = dbsub.add_parser("shrink", help="prune old trie checkpoints")
    sh.add_argument("--config", required=True)
    sh.add_argument("--retain", type=int, default=1000,
                    help="checkpoint depth to keep below the tip")
    sh.set_defaults(fn=cmd_db)
    rb = dbsub.add_parser("rollback", help="restore an older snapshot")
    rb.add_argument("--config", required=True)
    rb.add_argument("--height", type=int, required=True)
    rb.set_defaults(fn=cmd_db)
    cp = dbsub.add_parser(
        "compact", help="full LSM merge to a single table (lsm engine only)"
    )
    cp.add_argument("--config", required=True)
    cp.set_defaults(fn=cmd_db)
    ex = dbsub.add_parser(
        "export", help="dump every key/value to an engine-portable file"
    )
    ex.add_argument("--config", required=True)
    ex.add_argument("--out", required=True)
    ex.set_defaults(fn=cmd_db)
    im = dbsub.add_parser(
        "import",
        help="load an export into a FRESH store of the configured engine "
             "(the sqlite<->lsm migration path)",
    )
    im.add_argument("--config", required=True)
    im.add_argument("--dump", required=True)
    im.add_argument(
        "--expect-root",
        help="state hash (hex) from a trusted block header that the "
        "imported tip must match; without it a non-empty import is "
        "refused — the dump is never trusted blindly",
    )
    im.set_defaults(fn=cmd_db)

    en = sub.add_parser("encrypt", help="password-protect a wallet file")
    en.add_argument("--wallet", required=True)
    en.add_argument("--password", required=True)
    en.add_argument("--old-password", default=None)
    en.set_defaults(fn=cmd_encrypt)

    co = sub.add_parser(
        "console", help="interactive operator shell over a live node's RPC"
    )
    co.add_argument("--rpc", default="http://127.0.0.1:7071")
    co.add_argument("--timeout", type=float, default=10.0)
    co.add_argument(
        "--exec",
        help="run ';'-separated commands non-interactively and exit",
    )
    co.set_defaults(fn=cmd_console)

    tr = sub.add_parser(
        "trace",
        help="pull the node's era-lifecycle trace (Chrome trace_event JSON)",
    )
    tr.add_argument("--rpc", default="http://127.0.0.1:7071")
    tr.add_argument("--timeout", type=float, default=10.0)
    tr.add_argument("--out", help="write the trace JSON to this file")
    tr.add_argument(
        "--limit", type=int, default=None, help="cap the event count"
    )
    tr.add_argument(
        "--summary",
        action="store_true",
        help="print the per-span aggregate instead of the full trace",
    )
    tr.add_argument(
        "--era-report",
        action="store_true",
        help="print the per-era phase table (propose/RBC/BA/coin/TPKE/"
        "commit + idle split into wait buckets) from the merged flight "
        "recorder",
    )
    tr.add_argument(
        "--critical-path",
        action="store_true",
        help="print each era's longest blocking chain (phase and wait "
        "segments from era start to commit) from the merged flight "
        "recorder",
    )
    tr.set_defaults(fn=cmd_trace)

    ft = sub.add_parser(
        "fleet-trace",
        help="merge N nodes' traces into one clock-aligned Chrome trace "
        "with per-node lanes, plus the fleet era/skew table",
    )
    ft.add_argument(
        "--rpc",
        nargs="+",
        required=True,
        help="one RPC URL per node, e.g. http://10.0.0.1:7070",
    )
    ft.add_argument(
        "--names",
        help="comma-separated node labels matching --rpc order "
        "(default node0..nodeN-1)",
    )
    ft.add_argument("--timeout", type=float, default=10.0)
    ft.add_argument(
        "--samples",
        type=int,
        default=5,
        help="la_time pings per node for clock alignment",
    )
    ft.add_argument("--api-key", help="x-api-key if the RPC is gated")
    ft.add_argument("--out", help="write the merged trace JSON here")
    ft.set_defaults(fn=cmd_fleet_trace)

    de = sub.add_parser("decrypt", help="print a wallet's decrypted JSON")
    de.add_argument("--wallet", required=True)
    de.add_argument("--password", default=None)
    de.set_defaults(fn=cmd_decrypt)

    ch = sub.add_parser(
        "chaos",
        help="run a seeded fault scenario against an in-process devnet",
    )
    ch.add_argument("--n", type=int, default=4)
    ch.add_argument("--f", type=int, default=1)
    ch.add_argument("--eras", type=int, default=3)
    ch.add_argument("--seed", type=int, default=0)
    ch.add_argument("--drop", type=float, default=0.0,
                    help="per-message loss probability")
    ch.add_argument("--duplicate", type=float, default=0.0,
                    help="per-message duplication probability")
    ch.add_argument("--delay", type=float, default=0.0,
                    help="per-message delay probability")
    ch.add_argument("--reorder", type=float, default=0.0,
                    help="per-message reorder probability")
    ch.add_argument("--crash", action="append", default=[],
                    metavar="NODE@AT[:RESTART]",
                    help="crash schedule, repeatable (e.g. 3@50:400)")
    ch.add_argument("--partition", action="append", default=[],
                    metavar="A,B|C,D@AT[:HEAL]",
                    help="partition schedule, repeatable "
                         "(e.g. '0,1|2,3@30:500')")
    ch.add_argument("--engine", choices=["python", "native", "sqlite", "lsm"],
                    default="python",
                    help="consensus engine for fault runs; storage engine "
                         "(sqlite|lsm) for --crash-point runs")
    ch.add_argument("--crash-point", action="append", default=[],
                    metavar="NAME[@HIT]",
                    help="storage crash scenario: SIGKILL a child workload "
                         "at this pipeline point (see storage/crashpoints.py"
                         " for names), then fsck + resume; repeatable")
    ch.add_argument("--byzantine", default=None,
                    metavar="STRATEGY",
                    choices=["equivocate", "withhold", "relay", "spam",
                             "equivocate_votes"],
                    help="smart-malicious traitors (consensus/adversary.py):"
                         " equivocate (conflicting coin/TPKE shares per"
                         " slot), withhold (shares to only f+1 seeded"
                         " recipients), relay (seeded replay of captured"
                         " signed frames, spoofed origin), spam (flood"
                         " distinct coin slots past the latch budget),"
                         " equivocate_votes (AUX/CONF flip, python engine"
                         " only); prints per-era evidence + recovery report")
    ch.add_argument("--traitors", default=None,
                    metavar="I,J,...",
                    help="comma-separated traitor ids for --byzantine "
                         "(default: validators 0..f-1)")
    ch.add_argument("--wan", default=None, metavar="SPEC",
                    help="LinkShaper WAN matrix on every link (python "
                         "engine only), e.g. 'regions=us,eu;"
                         "default=40ms/5ms;intra=2ms;burst=0.01x8'")
    ch.set_defaults(fn=cmd_chaos)

    fu = sub.add_parser(
        "fleet-upgrade",
        help="zero-downtime rolling-upgrade drill: legacy-wire TCP fleet "
             "rolled node-by-node onto the LTRX wire under traffic, gated "
             "on /healthz + zero fleet missed eras",
    )
    fu.add_argument("--n", type=int, default=6)
    fu.add_argument("--f", type=int, default=1)
    fu.add_argument("--seed", type=int, default=0)
    fu.add_argument("--wan", default=None, metavar="SPEC",
                    help="LinkShaper spec shaping the fleet's loopback "
                         "links into emulated regions")
    fu.add_argument("--txs-per-era", type=int, default=8,
                    help="open-loop transactions paced in before each era")
    fu.add_argument("--warmup", type=int, default=1,
                    help="eras committed before the roll starts")
    fu.add_argument("--cooldown", type=int, default=1,
                    help="eras committed after every node is upgraded")
    fu.add_argument("--era-timeout", type=float, default=60.0)
    fu.set_defaults(fn=cmd_fleet_upgrade)

    fs = sub.add_parser(
        "fsck", help="scan storage invariants; repair or refuse"
    )
    fs.add_argument("--config", required=True)
    fs.add_argument("--deep", action="store_true",
                    help="full trie DFS + full index scans (slow)")
    fs.add_argument("--no-repair", action="store_true",
                    help="report only; repairable issues become fatal")
    fs.set_defaults(fn=cmd_fsck)

    args = p.parse_args(argv)
    # subprocess crash harness: a child `lachain-tpu run` executes the
    # parent's CrashPlan (no-op unless LACHAIN_CRASH_POINTS is set)
    from .storage.crashpoints import arm_from_env

    arm_from_env()
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
