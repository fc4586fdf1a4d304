"""Test configuration.

Force JAX onto a virtual 8-device CPU mesh BEFORE any backend
initializes (JAX_PLATFORMS=cpu is how tests select the CPU):
multi-chip sharding paths (pjit/shard_map over a Mesh) are exercised on
CPU devices in CI; real-TPU execution is `python chip_smoke.py` on the
chip.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
#: the mesh-marker seam: tier-1 defaults to an 8-device virtual CPU
#: mesh (mirroring the 8-chip target topology); set
#: JEPSEN_TPU_HOST_DEVICES=1 to run the whole suite single-device, or
#: any other count to exercise odd mesh shapes. An explicit
#: xla_force_host_platform_device_count in XLA_FLAGS wins.
_n_dev = os.environ.get("JEPSEN_TPU_HOST_DEVICES", "8")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={_n_dev}"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Two schedule tweaks.

    @pytest.mark.service tests run LAST: each daemon takes ownership of
    the process-wide dispatch plane and resets it (plus the resilience
    ledger) on teardown, so they run after every suite that assumes a
    quiet default engine rather than interleaving mid-alphabet.

    @pytest.mark.mesh tests need a real multi-device mesh: skip them
    when the forced host-platform device count (or the actual device
    count) is 1, so JEPSEN_TPU_HOST_DEVICES=1 runs stay green."""
    items.sort(key=lambda item: "service" in item.keywords)
    if len(jax.devices()) >= 2:
        return
    skip = pytest.mark.skip(reason="mesh tests need >=2 devices")
    for item in items:
        if "mesh" in item.keywords:
            item.add_marker(skip)
