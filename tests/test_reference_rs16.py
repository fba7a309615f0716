"""The plain GF(2^16) codec (perfbench/reference_rs16.py) against the
program's batched codec (ops/rs_batch.py) past 255 shards: the same shards
from the same payload, and the same payload back after n - k erasures, on
the numpy path and on the device program (run by jax on the CPU here)."""
import random

import pytest

from lachain_tpu.ops import rs_batch
from lachain_tpu.utils import metrics
from perfbench import reference_rs16 as ref

SIZES = [(256, 86), (300, 100)]  # N=256, f=85, the first past 255; a larger one
CASES = ["encode", "erase_first", "erase_last", "erase_random"]


def _device_products() -> float:
    return metrics.counter_value(
        "rs_matmul_total", labels={"path": "device", "bits": 16}
    )


def _erased(shards, n, k, case, rng):
    lost = {
        "erase_first": range(n - k),
        "erase_last": range(k, n),
        "erase_random": rng.sample(range(n), n - k),
    }[case]
    lost = set(lost)
    return [None if i in lost else s for i, s in enumerate(shards)]


@pytest.mark.parametrize("path", ["numpy", "device"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("n,k", SIZES)
def test_reference_codec_agrees_with_the_program(n, k, case, path, monkeypatch):
    if path == "device":
        monkeypatch.setenv("LACHAIN_RS_DEVICE", "1")
        monkeypatch.setattr(rs_batch, "_DEVICE_MIN_COLS", 1)
    else:
        monkeypatch.setenv("LACHAIN_RS_DEVICE", "0")
    rng = random.Random(f"{n}-{k}-{case}-{path}")
    payloads = [rng.randbytes(rng.randrange(0, 40 * k)) for _ in range(3)]
    before = _device_products()
    shards = rs_batch.encode_batch([(p, k, n) for p in payloads])
    assert shards == ref.encode_many(payloads, k, n)
    assert all(len(s) == n for s in shards)
    if case != "encode":
        holes = [_erased(s, n, k, case, rng) for s in shards]
        assert rs_batch.decode_batch([(h, k) for h in holes]) == payloads
        assert [ref.decode(h, k) for h in holes] == payloads
    ran = _device_products() - before
    assert (ran > 0) == (path == "device")


def test_reference_decode_refuses_what_cannot_be_a_codeword():
    n, k = 256, 86
    shards = ref.encode(b"payload", k, n)
    assert ref.decode(shards[: k - 1] + [None] * (n - k + 1), k) is None
    odd = [s + b"\x00" for s in shards]
    assert ref.decode(odd, k) is None
    mixed = [shards[0] + b"\x00\x00"] + shards[1:]
    assert ref.decode(mixed, k) is None
    with pytest.raises(ValueError):
        ref.encode(b"x", k, 1 << 16)
