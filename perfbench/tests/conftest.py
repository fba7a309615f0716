"""The benchmark's own rehearsals: they cost no chip time and run on the CPU.
Run them with `python -m pytest perfbench/tests -q -p no:cacheprovider`."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(
    0,
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
)
