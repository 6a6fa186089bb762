"""Where a run executes: the device report every entry point prints first,
and the persistent compile cache they share.

Tests run on the CPU (``JAX_PLATFORMS=cpu``, Pallas kernels interpreted);
the chip runs the same entry points with the kernels compiled
(``repro.kernels.common.resolve_interpret``). The report line names the
platform, ``device_kind`` and device count, so no output can be mistaken
for a run on another device.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# <repo>/.jax_cache: src/repro/launch/device.py -> parents[3] is the root.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here. Otherwise the cache is ``<repo>/.jax_cache``: a
    fixed path, because the path is part of what lets a later process find
    an entry again. Call it before the first compile; JAX fixes the cache
    location when it first compiles."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_report() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports device 0 and the
    number of visible devices."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def print_device_report() -> dict:
    """Print the one-line device report and return it."""
    rep = device_report()
    print(f"device: platform={rep['platform']} kind={rep['kind']} "
          f"count={rep['count']}")
    return rep
