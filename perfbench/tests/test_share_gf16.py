"""The `share_gf16` driver (hb256-share.quiet) without the chip: the script's
tables cover the eras the configuration allows for, the GF(2^16) codec
checks convict a wrong shard and a wrong root, a program whose committee
script keeps no record of validator 0's VALs fails the run in set-up, and
the RS roofline share counts the work PERF.md section 3 gives."""
import math
import time

import pytest

from lachain_tpu.ops import rs_batch
from perfbench import roofline, spec, xtrace
from perfbench.drivers import share_gf16
from perfbench.harness import Rehearsal, run_cell

CELL = "hb256-share.quiet"


class _Stop(Exception):
    pass


class _Bench:
    def __init__(self):
        self.said = []

    def say(self, msg):
        self.said.append(msg)


def test_script_tables_cover_warm_window_and_drain(monkeypatch):
    from lachain_tpu.consensus import committee_script
    from lachain_tpu.core import devnet

    cell = spec.load_cell(CELL)
    cfg = cell.config
    assert cfg["script"]["window_s"] == spec.load_benchmark()["run_seconds"]
    want = (
        int(cfg["warm_eras"])
        + math.ceil(cfg["script"]["window_s"] / cfg["script"]["era_floor_s"])
        + int(cfg["drain_eras_max"])
    )
    seen = {}

    def script(*_args, **kwargs):
        seen.update(kwargs)
        raise _Stop

    script.own_vals = committee_script.CommitteeScript.own_vals
    monkeypatch.setattr(committee_script, "CommitteeScript", script)
    monkeypatch.setattr(devnet, "devnet_keys", lambda n, f, seed: (None, [None] * n))
    driver = share_gf16.Driver(cell, _Bench())
    with pytest.raises(_Stop):
        driver.setup()
    assert seen["eras"] == want
    assert seen["txs_per_block"] // cfg["n"] == 39


class _Script:
    """What the driver's codec checks read of the committee script."""

    def __init__(self, own, tables):
        self._own, self.tables = own, tables

    def own_vals(self, era):
        return self._own.get(era, ({}, set()))


def _driver_with(own, table, n=256, f=85):
    driver = share_gf16.Driver.__new__(share_gf16.Driver)
    driver.cfg = {"n": n, "f": f}
    driver.bench = _Bench()
    driver.era = max(own)
    driver.window_last = driver.era
    driver.script = _Script(own, {driver.era: table})
    return driver


def _root(shards):
    from lachain_tpu.crypto import hashes

    return hashes.merkle_root(hashes.keccak256_batch(shards))


def _table(era, ciphertexts, roots):
    from lachain_tpu.consensus import committee_script, native_rt

    val = b"".join(
        native_rt.encode_seam_record(j, 0, native_rt.MT_VAL, agreement=j, root=r, era=era)
        for j, r in roots.items()
    )
    return committee_script.EraTable(era, {}, ciphertexts, val)


def test_codec_checks_pass_the_program_and_convict_a_wrong_shard_or_root():
    n, f = 256, 85
    k = n - 2 * f
    payloads = [bytes([j]) * (40 + j) for j in range(n)]
    coded = rs_batch.encode_batch([(p, k, n) for p in payloads])
    sent = {p: coded[0][p] for p in range(1, n)}
    own = {1: (sent, {_root(coded[0])})}
    peers = {j: payloads[j] for j in range(1, n)}
    table = _table(1, peers, {j: _root(coded[j]) for j in range(1, n)})
    assert _driver_with(own, table)._codec_failures() == []

    flipped = dict(sent)
    flipped[200] = bytes([flipped[200][0] ^ 1]) + flipped[200][1:]
    bad = _driver_with({1: (flipped, {_root(coded[0])})}, table)
    assert bad._codec_failures() == [
        "era 1: validator 0's shards are no plain GF(2^16) encoding of one "
        "payload (1 of 255 differ)"
    ]
    elsewhere = _driver_with({1: (sent, {_root(coded[1])})}, table)
    assert elsewhere._codec_failures() == [
        "era 1: validator 0's shards went out under another root"
    ]
    short = dict(sent)
    del short[200]
    assert _driver_with({1: (short, {_root(coded[0])})}, table)._codec_failures() == [
        "era 1: validator 0 sent VALs to 254 of 255 peers under 1 roots"
    ]
    wrong_root = {j: _root(coded[j]) for j in range(1, n)}
    wrong_root[9] = _root(coded[8])
    got = _driver_with(own, _table(1, peers, wrong_root))._codec_failures()
    assert got == [
        "era 1: slot 9's proposal, encoded by the plain GF(2^16) codec, does not "
        "give the root RBC delivered it under"
    ]


def test_a_program_without_the_vals_record_fails_in_set_up(monkeypatch):
    from perfbench.tests.test_rehearsal import TINY
    from lachain_tpu.consensus.committee_script import CommitteeScript

    monkeypatch.delattr(CommitteeScript, "own_vals")
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="own_vals"):
        run_cell(CELL, 7, 1.0, False, time.monotonic(), rehearsal=Rehearsal(config=TINY))
    assert time.monotonic() - t0 < 60


def test_rs_product_work_is_the_bit_matrix_product():
    flops, nbytes = roofline.rs_product_work(
        {"bits": 8, "rows": 64, "k": 22, "cols_padded": 16384}
    )
    assert flops == 2 * 512 * 176 * 16384  # 2.95 GFLOP (PERF.md section 3)
    assert nbytes == 512 * 176 + (22 + 64) * 16384
    flops16, nbytes16 = roofline.rs_product_work(
        {"bits": 16, "rows": 256, "k": 86, "cols_padded": 16384}
    )
    assert flops16 == 2 * 4096 * 1376 * 16384
    assert nbytes16 == 4096 * 1376 + (86 + 256) * 16384 * 2
    assert roofline.rs_product_work({"bits": 16, "rows": 256, "cols_padded": 8}) is None


def test_rs_roofline_share_over_the_profiled_scope():
    args = {"bits": 16, "rows": 256, "k": 86, "cols_padded": 16384}
    flops, _b = roofline.rs_product_work(args)
    peak = 197e12
    least = flops / peak  # compute-bound at these shapes
    spans = [
        {"name": "rs.device", "open": False, "start": 10.1, "args": args},
        {"name": "rs.device", "open": False, "start": 99.0, "args": args},  # outside
    ]
    ops = [
        xtrace.Op("jit__mm", 10.2, 10.2 + 4 * least, "/device:TPU:0", "XLA Modules"),
        xtrace.Op("jit__mm", 99.1, 99.2, "/device:TPU:0", "XLA Modules"),
        xtrace.Op("fusion", 10.3, 10.9, "/device:TPU:0", "XLA Ops"),
    ]
    share = roofline.rs_roofline_share(spans, ops, [(10.0, 11.0)], peak, 819e9)
    assert share == pytest.approx(0.25)
    assert roofline.rs_roofline_share(spans, ops, [(50.0, 51.0)], peak, 819e9) is None
    old = [dict(spans[0], args={k: v for k, v in args.items() if k != "k"})]
    assert roofline.rs_roofline_share(old, ops, [(10.0, 11.0)], peak, 819e9) is None
