"""Test configuration: force an 8-device virtual CPU mesh.

Sharding/collective tests run on a virtual CPU mesh (no multi-chip TPU
hardware in CI); the driver separately dry-runs the multi-chip path.

``XLA_FLAGS`` and ``JAX_PLATFORMS`` must be set before jax is imported
anywhere.
"""
import os

# GFTPU_TEST_TPU=1 keeps the real device visible so the
# skip-if-no-tpu markers (real-lowering golden-vector parity in
# test_gf256_pallas.py) actually run, and turns a backend that cannot
# initialize into a failure instead of a skip:
#   GFTPU_TEST_TPU=1 pytest tests/test_gf256_pallas.py \
#       tests/test_systematic.py -k silicon
_USE_TPU = os.environ.get("GFTPU_TEST_TPU") == "1"

if not _USE_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402,F401
