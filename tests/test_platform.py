"""`runtime/platform.py` — the start-up decisions of every executable.

The suite itself is a process that *ended up* on the CPU (conftest forces
the platform through jax.config), which is exactly the case the helper
must tell apart from a caller who asked for the CPU by name.
`tests/test_zz_chip_smoke.py` drives the executable built on it.
"""

import os

import jax
import pytest

from mosaic_tpu.runtime import platform as plat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_require_device_takes_the_cpu_only_when_asked_by_name(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    info = plat.require_device()
    assert info == {
        "platform": "cpu",
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    with pytest.raises(plat.PlatformError, match="refuses"):
        plat.require_device(allow_cpu=False)
    # the same CPU, not asked for: a fallback, and refused
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(plat.PlatformError, match="not a TPU"):
        plat.require_device()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(plat.PlatformError):
        plat.require_device()


def test_per_chip_unit_never_names_a_chip_on_the_cpu():
    assert plat.per_chip("points/sec", {"platform": "tpu"}) == "points/sec/chip"
    assert "chip" not in plat.per_chip("points/sec", {"platform": "cpu"})


def test_interpret_rule_is_the_platform():
    assert plat.interpret_kernels() is True  # this suite runs on the CPU


@pytest.fixture
def restore_cache_config():
    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_enable_compilation_cache",
    )
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
    monkeypatch, restore_cache_config, tmp_path
):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert plat.configure_compile_cache() == str(tmp_path)
    # JAX's own knob: the code sets no directory beside it
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, plat.COMPILE_CACHE_DIRNAME)
    assert plat.configure_compile_cache() == fixed
    assert plat.configure_compile_cache() == fixed  # no pid, no clock
    assert jax.config.jax_compilation_cache_dir == fixed
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # the cache is for the chip: on XLA:CPU it stays off (a cache-loaded
    # CPU executable cannot be re-serialised by the ProgramStore)
    assert jax.config.jax_enable_compilation_cache is False
