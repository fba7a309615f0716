"""Child process of traffic.Traffic: signs the seed's transaction stream and
writes it to stdout as length-prefixed raw transactions until the pipe
closes. It runs on the native host backend and never imports jax, so it can
share a machine with the process that owns the chip."""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> None:
    from perfbench.traffic import _FRAME, signed_stream

    spec = json.loads(sys.argv[1])
    out = sys.stdout.buffer
    try:
        for raw in signed_stream(spec["mix"], spec["seed"], spec["chain_id"]):
            out.write(_FRAME.pack(len(raw)) + raw)
    except BrokenPipeError:
        os._exit(0)  # the benchmark closed its end: done


if __name__ == "__main__":
    main()
