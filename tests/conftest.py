"""Test configuration: run JAX on a virtual 8-device CPU mesh.

This mirrors the reference's CI strategy of testing multi-backend code on
CPU-only runners (ref: .circleci/config.yml, SURVEY.md §4): CPU JAX is the
"fake backend"; multi-chip sharding logic is validated on
``--xla_force_host_platform_device_count=8`` virtual devices.
"""

import os

# set before anything imports JAX: the platform and the device count are
# read once, at backend initialisation
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
