"""Test configuration.

Tier-1 is a CPU suite by design: multi-chip sharding tests run on a virtual
8-device CPU mesh (xla_force_host_platform_device_count), Pallas kernels run
with ``interpret=True`` passed explicitly, and nothing here may claim a chip
— a chip belongs to one process, and on a chip host ``JAX_PLATFORMS`` may
name the TPU first. What only happens on the TPU (Mosaic compiles, donation
over real H2D, the staging pool's recycling, the all-chips mesh) is
exercised by ``chip_smoke.py`` through the chip tool, not by pytest.

The platform is pinned twice on purpose: the env var before jax is imported
(it also reaches every subprocess the tests spawn), and ``jax.config.update``
in case a pytest plugin imported jax first (backends are created lazily, so
the config still wins).
"""

import os
import sys

# XLA reads XLA_FLAGS from the environment at (lazy) backend creation, so
# setting it here is still early enough — as long as no test imported jax and
# created a backend before conftest ran, which pytest's conftest-first
# ordering guarantees.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocess the tests spawn

import jax

jax.config.update("jax_platforms", "cpu")

# Keep test logs quiet and deterministic.
os.environ.setdefault("DMLC_LOG_STACK_TRACE", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running variants excluded from the tier-1 run "
        "(-m 'not slow')",
    )
