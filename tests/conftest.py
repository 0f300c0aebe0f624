"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Must run before any ``jax`` import (SURVEY.md §4 "Distributed tests": fake a
pod slice with ``xla_force_host_platform_device_count``, the moral
equivalent of the reference's in-process network dict).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """``bigmem`` tests (multi-GB RSS, config-5 scale) never run in tier-1:
    the tier-1 command only deselects ``slow``, so the exclusion is an
    explicit skip here, lifted by RUN_BIGMEM=1 for machines that opt in."""
    if os.environ.get("RUN_BIGMEM") == "1":
        return
    skip = pytest.mark.skip(reason="bigmem: set RUN_BIGMEM=1 to run")
    for item in items:
        if "bigmem" in item.keywords:
            item.add_marker(skip)
