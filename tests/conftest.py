"""Test configuration.

Tests never require TPU hardware: JAX-dependent tests run on a virtual
8-device CPU mesh (the multi-chip sharding path is validated the same way the
driver's dryrun does).  These env vars must be set before the first
``import jax`` anywhere in the test process.
"""

import os
import sys

import pytest

# Force CPU even when the ambient environment points JAX at a TPU: the test
# suite validates logic and sharding on an 8-device virtual mesh; real-TPU
# runs happen via chip_smoke.py.  DSI_TEST_PLATFORM overrides for TPU smoke
# runs.  Naming the CPU here is also what lets every device entry point a
# test spawns pass utils/platformpin.require_device.
_platform = os.environ.get("DSI_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Tier-1 uses NO persistent compile cache, in this process or in the CLIs
# it spawns: XLA:CPU compiles these programs in seconds, a hit from
# XLA:CPU's AOT loader logs a machine-feature warning per program, and
# tests must not share state through <checkout>/.jaxcache (the chip's).
# The tests of utils/compilecache.py switch it back on for their own
# temporary directory.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

try:
    import jax

    jax.config.update("jax_platforms", _platform)
except ImportError:
    pass


# ── keep one long pytest process under vm.max_map_count ────────────────
#
# XLA:CPU maps a few memory regions per compiled executable and never
# unmaps them while the executable is referenced; this suite compiles
# thousands of programs in ONE process and every cache here (jax's jit
# caches, backends/aotcache's memo, the per-shape lru_caches) keeps them
# alive.  Linux allows 65,530 mappings per process; past that mmap fails
# inside LLVM and the next compile is a segmentation fault — seen at ~84%
# of tier-1.  A job process never gets near the limit; the test process
# drops its compiled programs between modules once it is two thirds there.

_MAP_BUDGET = 40_000


def _mapping_count() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # not Linux: no such limit to watch
        return 0


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_programs_near_the_map_limit():
    yield
    if _mapping_count() < _MAP_BUDGET:
        return
    import functools
    import gc

    import jax

    from dsi_tpu.backends import aotcache

    aotcache._memo.clear()
    for obj in gc.get_objects():
        if isinstance(obj, functools._lru_cache_wrapper):
            obj.cache_clear()
    jax.clear_caches()
    gc.collect()
