import os
import sys

import pytest

# Tests run on the single real CPU device by default (the 512-device
# override lives ONLY in launch/dryrun.py, per the dry-run spec).
# REPRO_MULTIDEVICE=1 mirrors the CI tier1-multidevice job locally: the
# 8-device forced-host-platform flag must land before jax initializes, so
# it is applied here, ahead of the import below. Tests that need several
# devices carry @pytest.mark.multidevice and skip on a 1-device run.
if os.environ.get("REPRO_MULTIDEVICE"):
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_enable_x64", False)

MULTIDEVICE_HELP = ("needs >= 2 jax devices: run with REPRO_MULTIDEVICE=1 "
                    "(or XLA_FLAGS=--xla_force_host_platform_device_count=8,"
                    " as the CI tier1-multidevice job does)")


def pytest_collection_modifyitems(config, items):
    if len(jax.devices()) >= 2:
        return
    skip = pytest.mark.skip(reason=MULTIDEVICE_HELP)
    for item in items:
        if "multidevice" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def chip_devices():
    """The device group multidevice tests carve sub-meshes from; skips
    when the platform has only one device (mirrors the marker)."""
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip(MULTIDEVICE_HELP)
    return devs
