"""The one place that decides "TPU or CPU" — and refuses to guess.

Every device-dependent choice in the package (compiled Mosaic kernel vs the
Pallas interpreter, Pallas kernel vs the XLA reference path, buffer donation)
asks :func:`on_tpu`.  The CPU answer is only given when the caller *asked*
for the CPU platform (``JAX_PLATFORMS=cpu`` or
``jax.config.update("jax_platforms", "cpu")`` — what the test suite does).
A machine that was meant to have a chip but on which JAX silently fell back
to the CPU is an error here, not a slower mode: otherwise every kernel turns
into interpreted XLA and the run looks green.

Also home of the two helpers the chip-facing scripts share: the CPU device
that sits next to the TPU (host-side quantization / init), and the
persistent compile cache placement.
"""

from __future__ import annotations

import os


def _requested_platform() -> str:
    """First entry of the platform list JAX was told to use (env
    ``JAX_PLATFORMS`` or the ``jax_platforms`` config); ``""`` when JAX was
    left to pick for itself."""
    import jax

    return (jax.config.jax_platforms or "").split(",")[0].strip().lower()


def on_tpu() -> bool:
    """True on a TPU backend; False when the CPU platform was requested
    explicitly; raises on anything else (an unrequested CPU fallback, a
    GPU) so no caller can take the interpreted/reference path by accident.
    Trace-time only — never call this at import."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return True
    if backend == "cpu" and _requested_platform() == "cpu":
        return False
    raise RuntimeError(
        f"deepspeed_tpu: default JAX backend is {backend!r} but no TPU was "
        "found and the CPU platform was not requested.  Pallas kernels "
        "would run interpreted and every TPU dispatch would take its XLA "
        "reference path.  Fix the accelerator start-up, or set "
        "JAX_PLATFORMS=cpu to run on the CPU on purpose (tests do).")


def interpret_kernels() -> bool:
    """``interpret=`` default for every ``pl.pallas_call`` in the package."""
    return not on_tpu()


def host_cpu_device():
    """This process's CPU device, for work that must not touch HBM
    (host-side weight quantization, ZeRO-Infinity host init).  Needs the
    CPU platform registered next to the accelerator: JAX does that unless
    ``JAX_PLATFORMS`` names the accelerator alone."""
    import jax

    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "deepspeed_tpu needs the CPU platform registered next to the "
            f"accelerator for host-side work, but JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r} excludes it — set "
            "JAX_PLATFORMS='tpu,cpu' or leave it unset") from e


#: compiled programs smaller than this are not worth a cache entry by JAX's
#: default (1 s); the serving programs of a small model compile faster than
#: that and a fresh machine pays for each of them again, so keep everything
_CACHE_MIN_COMPILE_SECS = 0.0


def enable_compile_cache(checkout_root: str) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Called by the scripts that run on the chip (``chip_smoke.py``,
    ``chipbench/run.py``) and by the test harness (``tests/conftest.py``,
    whose root is a directory under the system's temp directory) before
    their first compile — never at package import.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and this
    sets no other directory; otherwise the cache lives at the fixed,
    git-ignored ``<checkout_root>/.jax_cache`` (the path is part of the
    cache key, so it is never derived from a temp name, pid or time)."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(os.path.abspath(checkout_root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _CACHE_MIN_COMPILE_SECS)
    return cache_dir
