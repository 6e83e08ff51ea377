"""Test harness: virtual 8-device CPU mesh + float64 enabled.

Mirrors the reference's `SparkSuite` local[4] harness
(`src/test/scala/.../test/SparkSuite.scala:44`): distribution semantics are
exercised without real hardware by forcing 8 XLA host-platform devices.

The suite runs on the CPU whatever the machine holds: the platform is
forced through jax.config before the first backend touch (tier-1 also
sets JAX_PLATFORMS=cpu). XLA_FLAGS is read lazily at first backend init,
which has not happened yet when conftest loads.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {devs}"
    return devs


@pytest.fixture(autouse=True, scope="module")
def _fresh_ops_plane():
    """The process-wide health and SLO monitors keep a 60 s window, so
    the faults one test file injects would surface as
    ``health_transition`` events in the captures of whichever file the
    same worker runs next: each file starts them empty."""
    from mosaic_tpu.obs import health, slo

    health.MONITOR.reset()
    slo.MONITOR.reset()
    yield
