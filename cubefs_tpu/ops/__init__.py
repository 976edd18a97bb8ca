"""Device kernels (JAX / Pallas) and their host-side algebra.

Every JAX user in the tree reaches JAX through this package, so the one
process-wide JAX setting the repo makes lives here and runs at package
import, before anything can compile.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache(environ=os.environ) -> str | None:
    """Place JAX's persistent compilation cache; returns the directory
    (None when this process keeps none).

    ``JAX_COMPILATION_CACHE_DIR`` set: the operator placed it, the code
    sets no path. Unset: ``<checkout>/.jax_cache`` — a fixed path,
    because the directory is part of the cache key's environment and a
    path that moves (tempfile, pid, time) never hits. Either way the
    minimum-compile-time threshold drops to 0: the codec kernels
    compile in 0.2-2 s each, mostly under JAX's default 1 s floor, and
    every drained batch width is its own jit shape. And a source
    location carries its innermost frame only: XLA strips locations
    from a module before it keys it, but not from the Mosaic payload of
    a Pallas program, and with ten frames of call stack in them that key
    named whoever called the program first — the bare or the phased
    engine call, a traced run's wrappers, any edited caller — so one
    tree's processes kept missing each other's entries (9, 4, 3, 0 of
    40). (Not ``jax_include_full_tracebacks_in_locations``: turning
    that off renames the kernel in the device trace.)

    A process pinned to CPU (``JAX_PLATFORMS=cpu``: the test suite, the
    launcher's non-owner roles) is left alone: XLA:CPU executables are
    tied to the build machine's CPU features (the loader warns of
    SIGILL on every hit) and the CPU-side programs are small."""
    if environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    path = environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    return path


COMPILE_CACHE_DIR = configure_compile_cache()


def require_tpu() -> list:
    """For a process meant to own the chip (chip_smoke.py, cellbench):
    ask for the TPU by name and return its devices, or raise. With
    JAX_PLATFORMS unset JAX registers the TPU ``fail_quietly`` and hands
    a process that cannot get the chip — none attached, or held by
    another process — the CPU at INFO level; an explicit platform makes
    that a start-up error instead."""
    if not os.environ.get("JAX_PLATFORMS"):
        jax.config.update("jax_platforms", "tpu")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: the default JAX backend is {devs[0].platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); this "
            f"entry point measures the chip and does not fall back")
    return devs
