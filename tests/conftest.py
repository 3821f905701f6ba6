"""Test-session environment: force JAX onto CPU with 8 virtual devices so
multi-chip sharding (mesh/pjit/shard_map paths) is exercised without TPU
hardware. Must run before the first `import jax` anywhere in the suite."""

import os

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

from eth_consensus_specs_tpu.utils.cache import enable_persistent_cache

enable_persistent_cache()

# Observability: per-test kernel counters + run-level obs_report.json
# (eth_consensus_specs_tpu/test_infra/obs_plugin.py). The fixture import
# makes `kernel_counters` available suite-wide.
from eth_consensus_specs_tpu.test_infra.obs_plugin import (  # noqa: E402,F401
    ObsPlugin,
    kernel_counters,
)


def pytest_configure(config):
    config.pluginmanager.register(ObsPlugin(str(config.rootpath)), "eth-specs-obs")


@pytest.fixture
def reference_tree():
    """For a test that compiles the reference markdown through specc:
    skipped, with the reason, where the checkout is not mounted."""
    from tests.parity.helpers import require_reference

    require_reference()
