"""Test harness config.

Mirrors the reference's `new SparkContext("local[N]")` trick (SURVEY.md §4):
the full distributed path runs in one process by giving JAX 8 virtual CPU
devices. Must run before jax initializes a backend.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax reads JAX_PLATFORMS when it is imported; if something imported it
# before this file the variable above came too late, so say it through
# jax.config as well, before any backend initializes.
import jax

jax.config.update("jax_platforms", "cpu")
# fp32 matmuls for oracle-parity tests
jax.config.update("jax_default_matmul_precision", "highest")

# persistent compilation cache: most fast-loop wall time is XLA recompiles
# of the same programs run-over-run — warm runs skip them. The directory
# is JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.cache/jax; the
# threshold is lowered because the suite's programs are many and small.
from bigdl_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache(min_compile_time_secs=0.05)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.RandomState(42)


# -- one-compiled-program guards -------------------------------------------
# Canonical home: tests/compile_guards.py (a plain, side-effect-free
# module — import THAT in test files; importing tests.conftest would
# load a second copy of this module next to pytest's own instance and
# re-run the jax/XLA session setup above).  Re-exported here so the
# guard is discoverable where fixtures live.
from tests.compile_guards import (  # noqa: E402,F401
    assert_compile_count, compile_count)
