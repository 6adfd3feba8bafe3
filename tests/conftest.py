"""Test env: force JAX onto a virtual 8-device CPU platform *before* jax is
imported anywhere (SURVEY.md §4 — multi-core without a cluster), and make the
repo root importable without installation."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# No persistent compilation cache in tests (this process and every child it
# starts): the v5e compile tests would write TPU entries into the checkout,
# and the checkout is what gets copied to the chip machine.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
