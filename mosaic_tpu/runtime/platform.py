"""Start-up decisions every executable makes, made once here.

- **Which platform am I on** — :func:`require_device`. A measurement
  surface (``chip_smoke.py``, ``benchmark/run.py``) runs on
  the TPU, or on the CPU only when the caller asked for the CPU by name
  (``JAX_PLATFORMS=cpu``). A process that merely *ended up* on the CPU —
  no chip, a plug-in that failed to load — raises instead of printing
  CPU numbers under a device's name.
- **Where is the compile cache** — :func:`configure_compile_cache`. A
  set ``JAX_COMPILATION_CACHE_DIR`` is JAX's own knob and is left alone;
  otherwise the cache lives at one fixed path inside the checkout. The
  path is part of the cache key, so it is never derived from a temp
  dir, a pid or a clock. The cache serves accelerators only: on the CPU
  platform it is switched off (see the function).
- **Do Pallas kernels interpret** — :func:`interpret_kernels`. Only on
  the CPU, decided from the platform and nowhere else, so no spelling
  of a backend name can put an interpreted kernel on a chip.

- **How fine is the device's arithmetic** — :func:`arithmetic_eps`. An
  epsilon band has to be as wide as the arithmetic that computes under
  it is coarse, and ``np.finfo`` does not know that this chip has no
  float64 unit.

Executables call the first two; the library and the test harness do
not (tier-1 pins cold-compile counts, which a warm cache would zero).
"""

from __future__ import annotations

import os

#: the in-checkout cache directory (listed in .gitignore)
COMPILE_CACHE_DIRNAME = ".jax_cache"


class PlatformError(RuntimeError):
    """The process is not on the platform a measurement needs."""


def cpu_requested() -> bool:
    """Did the caller ask for the CPU by name (``JAX_PLATFORMS=cpu``)?"""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require_device(allow_cpu: bool = True) -> dict:
    """``{"platform", "kind", "count"}`` of the default backend as JAX
    reports it, or :class:`PlatformError` unless the platform is ``tpu``
    — or it is ``cpu`` *and* ``JAX_PLATFORMS=cpu`` asked for exactly that
    (``allow_cpu=False`` refuses the CPU outright)."""
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if info["platform"] == "tpu":
        return info
    if allow_cpu and info["platform"] == "cpu" and cpu_requested():
        return info
    raise PlatformError(
        f"running on {info['platform']} ({info['kind']} x{info['count']}), "
        "not a TPU: this program reports device numbers and takes the "
        "CPU only on an explicit JAX_PLATFORMS=cpu"
        + ("" if allow_cpu else " — which this entry point refuses")
    )


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere durable and
    return the directory.

    Small programs are cached too (the default 1 s floor would skip the
    low serve-ladder rungs, which are most of a warm-up's compiles).

    On the CPU platform the cache is switched off instead. An XLA:CPU
    executable that was *loaded* from the cache cannot be serialised
    again (jaxlib 0.9.0: the `ProgramStore` payload then dies at its
    first execution with ``NOT_FOUND: Function ... not found``; the TPU
    round trip is fine), XLA:CPU cache entries are bound to the build
    machine's CPU features (the loader warns of SIGILL), and CPU
    compiles take seconds — the cache is for the chip."""
    import jax

    if jax.devices()[0].platform == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if placed:
        return placed
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    path = os.path.join(root, COMPILE_CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def interpret_kernels() -> bool:
    """THE rule for ``pallas_call(interpret=...)``: interpret only when
    the platform is ``cpu`` (there is no Mosaic there); every other
    platform compiles the kernel or fails loudly."""
    import jax

    return jax.devices()[0].platform == "cpu"


#: mantissa bits the TPU's emulated float64 keeps (a pair of float32
#: words; measured, ``PERF.md`` section 6: "f64 on the chip is emulated
#: (~46 bits)"), against IEEE's 52: 64 times coarser than ``np.finfo``
TPU_F64_MANTISSA_BITS = 46


def arithmetic_eps(dtype, platform: str | None = None) -> float:
    """The relative rounding step of ``dtype`` as ``platform`` (default:
    the process's own) really computes in it: ``np.finfo(dtype).eps``
    everywhere but for float64 on a TPU, which has no float64 unit and
    emulates it at about 46 bits (``2**-46``, 64 times numpy's). THE
    rule for every epsilon band sized from the arithmetic — a band
    sized from ``np.finfo`` there lets the device decide contact cases
    it cannot see."""
    import numpy as np

    dt = np.dtype(dtype)
    if platform is None:
        import jax

        platform = jax.devices()[0].platform
    if dt == np.float64 and platform == "tpu":
        return float(2.0 ** -TPU_F64_MANTISSA_BITS)
    return float(np.finfo(dt).eps)


def per_chip(unit: str, info: dict) -> str:
    """``unit + "/chip"`` on a TPU. A CPU run names the CPU instead, so
    a rate from XLA:CPU is never filed under a device's unit."""
    return f"{unit}/chip" if info["platform"] == "tpu" else f"{unit}/cpu-run"
