"""Tests of the benchmark's own code. Run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They are not collected by the tier-1 command, which reads ``tests/``.
Nothing here loads libtpu or describes a topology.
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
