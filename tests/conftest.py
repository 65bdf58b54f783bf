"""Test configuration.

Tests run on the CPU: ``JAX_PLATFORMS=cpu`` selects it (the driver's
command sets it), and ``jax.config.update`` below pins it for a bare
``pytest`` too. ``XLA_FLAGS`` gives the CPU a virtual 8-device mesh, so
the sharded engine's multi-chip paths run without TPU hardware; it is
read when the CPU client starts, so setting it here, before any backend
use, still works. On a chip the program runs through ``chip_smoke.py``
(README "Running it").
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running parity checks (deselect with -m 'not slow')"
    )
