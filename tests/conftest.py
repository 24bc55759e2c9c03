"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's "multi-node without a cluster" strategy
(``test/.../optim/DistriOptimizerSpec.scala:112`` runs local[1] with
``Engine.setNodeAndCore`` overrides): all tests run on the XLA CPU backend
with 8 virtual devices so distributed/sharding code paths execute for real.
``jax.config.update`` (not only the environment) pins the platform, so the
suite stays on the CPU even when started on a machine that has a chip.
"""

import os
import sys

# --xla_backend_optimization_level=0: the suite is dominated by LLVM
# compiling hundreds of tiny programs whose run time is nothing, and a fresh
# checkout starts with a cold compile cache. Measured on 8 cores, cold:
# 708 s at the default level, 604 s at level 0 with 27 more tests, against
# the 870 s tier-1 timeout. Put first, so an XLA_FLAGS from outside wins.
os.environ["XLA_FLAGS"] = ("--xla_backend_optimization_level=0 "
                           + os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent XLA compilation cache: the suite is compile-dominated and test
# shapes are stable run-to-run, so repeat runs in one checkout skip almost
# all compiles (JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache)
from bigdl_tpu.utils.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()

import pytest  # noqa: E402


@pytest.fixture
def multi_device_cpu():
    """Gate for tests needing the 8-device virtual CPU mesh (tp sharding,
    fleet sub-slices). Skips — instead of failing on mesh construction —
    when the backend came up with fewer devices (XLA_FLAGS overridden
    from outside)."""
    n = jax.device_count()
    if n < 8:
        pytest.skip("needs 8 virtual CPU devices, backend has %d" % n)
    return jax.devices()
