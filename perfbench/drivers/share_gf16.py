"""Driver `share_gf16`: the `share` driver for a committee past 255
validators, where RBC codes over GF(2^16) (ops/rs_batch.py). `correct` holds
everything `share` checks, and holds the codec to the plain one
(perfbench/reference_rs16.py), symbol for symbol, on what crossed the
engine's seam:

* every era, validator 0's own proposal: the shards its VALs gave the n - 1
  peers decode, by the plain codec, to a payload whose plain encoding holds
  those same shards, and whose Merkle root is the one root the VALs carried;
* the window's last era: every peer's proposal, encoded by the plain codec,
  gives the root its VALs carried to validator 0, which RBC delivered under
  (`share` holds every era's ACS to all n slots).

A committee of 255 or fewer (the CPU rehearsal's) codes over GF(2^8), and
neither check applies.

Both read what the committee script saw (`CommitteeScript.own_vals`); a
program whose script keeps no such record cannot run the cell and says so
in set-up. A traced run also notes `rs_mm_roofline_share`, the RS
products' share of the roofline over the profiled era (perfbench/
roofline.py), and every run says the process's peak host memory.
"""
from __future__ import annotations

import json
import resource
import time

from .. import reference_rs16, roofline, xtrace
from ..spec import BENCH_DIR
from ..traffic import Load
from . import share


class _NoClientsYet:
    """The load through warm(): joins the script's tables, as the load's
    start does, and hands nothing over."""

    def __init__(self, script, bench):
        self._script, self._bench = script, bench

    def start(self) -> None:
        waited = self._script.join()
        self._bench.say(f"committee script: tables ready, waited {waited:.1f} s")

    def due(self) -> list:
        return []

    def hand_over(self, due_times) -> None:
        pass


class Driver(share.Driver):
    def __init__(self, cell, bench):
        super().__init__(cell, bench)
        self.window_last = None  # the window's last era

    def setup(self) -> None:
        from lachain_tpu.consensus.committee_script import CommitteeScript

        if not hasattr(CommitteeScript, "own_vals"):
            raise SystemExit(
                "perfbench: this program's committee script keeps no record of "
                "validator 0's VALs (CommitteeScript.own_vals), so the cell's "
                "GF(2^16) checks cannot run"
            )
        super().setup()

    def warm(self) -> None:
        # the warm era runs before the clients' clock starts: validator 0
        # carries 39 of the ~74 transactions they send an era, so a loaded
        # warm era would add a drain era and a re-executed block to a run
        # that has little of its time limit to spare
        load, self.load = self.load, _NoClientsYet(self.script, self.bench)
        try:
            super().warm()
        finally:
            self.load = load
        Load.start(load)  # the tables are joined: only the clock starts

    def run_window(self, seconds: float) -> None:
        super().run_window(seconds)
        self.window_last = self.era
        self.bench.say(f"eras so far (s): {', '.join(f'{s:.3f}' for s in self.era_s)}")

    def drain(self) -> None:
        t0, era = time.monotonic(), self.era
        super().drain()
        self.bench.say(
            f"drain: {self.era - era} eras in {time.monotonic() - t0:.1f} s, "
            f"{self.bench.record.outstanding()} attempted transactions left"
        )
        if self.cell.trace:
            self._note_roofline()

    # -- correct --------------------------------------------------------------------
    def check(self) -> list:
        t0 = time.monotonic()
        wrong = super().check()
        t1 = time.monotonic()
        wrong += self._codec_failures()
        self.bench.say(
            f"check: {t1 - t0:.1f} s the committee's, {time.monotonic() - t1:.1f} s "
            f"the codec's"
        )
        return wrong

    def _codec_failures(self) -> list:
        from lachain_tpu.consensus import native_rt
        from lachain_tpu.crypto import hashes

        n, f = int(self.cfg["n"]), int(self.cfg["f"])
        k = n - 2 * f
        if n <= 255:
            # a committee GF(2^8) codes (the rehearsal's N=4): nothing to hold
            # to the plain GF(2^16) codec
            self.bench.say(f"GF(2^16) codec: not used at n={n}, nothing checked")
            return []

        def root_of(shards) -> bytes:
            return hashes.merkle_root(hashes.keccak256_batch(shards))

        wrong = []
        for era in range(1, self.era + 1):
            sent, roots = self.script.own_vals(era)
            if sorted(sent) != list(range(1, n)) or len(roots) != 1:
                wrong.append(
                    f"era {era}: validator 0 sent VALs to {len(sent)} of {n - 1} "
                    f"peers under {len(roots)} roots"
                )
                continue
            held = [None] + [sent[p] for p in range(1, n)]
            payload = reference_rs16.decode(held, k)
            plain = reference_rs16.encode(payload, k, n) if payload is not None else []
            if plain[1:] != held[1:]:
                bad = sum(a != b for a, b in zip(held[1:], plain[1:]))
                wrong.append(
                    f"era {era}: validator 0's shards are no plain GF(2^16) encoding "
                    f"of one payload ({bad if plain else n - 1} of {n - 1} differ)"
                )
            elif root_of(plain) not in roots:
                wrong.append(f"era {era}: validator 0's shards went out under another root")
        era = self.window_last
        table = self.script.tables.get(era)
        slots = list(range(1, n))
        if table is None:
            wrong.append(f"era {era}: the committee script holds no table of it")
        else:
            roots = {
                r.agreement: r.root for r in native_rt.decode_seam_records(table.val)
            }
            encoded = reference_rs16.encode_many(
                [table.ciphertexts[j] for j in slots], k, n
            )
            for slot, shards in zip(slots, encoded):
                if root_of(shards) != roots.get(slot):
                    wrong.append(
                        f"era {era}: slot {slot}'s proposal, encoded by the plain "
                        f"GF(2^16) codec, does not give the root RBC delivered it under"
                    )
        self.bench.say(
            f"GF(2^16) codec: validator 0's own proposal in {self.era} eras and "
            f"{len(slots)} peer proposals of era {era} held to the plain codec"
        )
        return wrong

    # -- the RS products' roofline share --------------------------------------------
    def _note_roofline(self) -> None:
        import jax

        from lachain_tpu.utils import tracing

        with open(BENCH_DIR / "peaks.json", encoding="utf-8") as fh:
            peak = json.load(fh)["devices"].get(jax.devices()[0].device_kind)
        if peak is None:
            return  # no peaks for this device (a rehearsal on the CPU)
        how = {**peak["trace"], **self.cfg.get("trace", {})}
        record = self.bench.record
        scopes = [(lo, hi) for lo, hi, _p, tag in record.slices if tag == "window"]
        ops = []
        for _lo, _hi, path, tag in record.slices:
            if tag == "window":
                ops += xtrace.device_ops(
                    xtrace.dump_xplane(path), how["plane_prefix"], how["op_lines"]
                )
        share_ = roofline.rs_roofline_share(
            tracing.snapshot(),
            ops,
            scopes,
            peak["bf16_tflops"] * 1e12,
            peak["hbm_gb_per_s"] * 1e9,
        )
        if share_ is not None:
            self.bench.note("rs_mm_roofline_share", 100.0 * share_)

    def close(self) -> None:
        try:
            super().close()
        finally:
            rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
            self.bench.say(f"host peak RSS of this process: {rss:.2f} GiB")
