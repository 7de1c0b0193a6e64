"""The benchmark's own tests run on the CPU, like the repository's: a
virtual 8-device mesh stands in for a host's chips, and no test may claim
a chip (``python3 -m pytest benchmarks/tests -q`` from the root)."""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH, os.path.dirname(BENCH)):  # `harness`, then `dmlc_tpu`
    if path not in sys.path:
        sys.path.insert(0, path)
