"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's off-cluster test strategy (SURVEY.md §4): the bulk of
distributed logic is tested without real hardware. JAX analog of the
reference's threads-based `harness/tests/parallel.py` fixture: force 8 host
CPU devices so Mesh/pjit/shard_map paths compile and run everywhere.
"""
import os

# Set before the first `import jax` (nothing imports it ahead of this
# file) and inherited by every subprocess the tests start: the suite
# never touches an accelerator, even on a machine that has one.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]
