"""Test configuration: force an 8-device virtual CPU platform.

Mirrors the reference's single-process multi-peer raft tests
(test/unit_test/test_raft_node.cc:125-199): all "distributed" behavior is
exercised in one process. Here the device mesh itself is virtualized so
sharding/collective code paths compile and run without TPU hardware.

Must run before jax is imported anywhere.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Also through the config API, before any backend initializes: a jax that
# was imported before this file ran has already read its environment.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Make the repo root importable regardless of pytest rootdir.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
