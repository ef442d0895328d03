"""pytest harness: builds the C++ core once per session, then runs both the
C++ unit-test binary (tests/test_cpp.py) and the Python-level tests.

JAX tests run on a virtual 8-device CPU mesh (multi-chip sharding is
validated without hardware, per the driver's dryrun_multichip contract).
"""
import os
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

sys.path.insert(0, str(REPO))  # brpc_tpu, __graft_entry__, chip_smoke

# Force a deterministic virtual 8-device CPU platform for all JAX tests
# BEFORE jax is imported anywhere. Unconditional override: tests and the
# driver's dryrun are specified on the virtual 8-device CPU mesh, whatever
# accelerator the host has (chip_smoke.py is what runs on the chip).
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (after the env setup above, by design)

jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def cpp_build():
    from brpc_tpu import native

    return native.build()


@pytest.fixture(scope="session")
def cpp_tests_bin(cpp_build):
    return cpp_build / "cpp_tests"
