"""Process set-up shared by the entry points that run on a device.

``enable_compile_cache`` turns on JAX's persistent compilation cache, and
``grid_mesh`` builds the BFS grid mesh from ``jax.devices()``, failing with
a clear message when the platform has fewer devices than the grid needs.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the fixed cache path used when ``JAX_COMPILATION_CACHE_DIR`` is unset;
#: the path is part of the cache key, so it never depends on a temporary
#: name, a process id or the time
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache lives at
    ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def grid_mesh(rows: int, cols: int) -> jax.sharding.Mesh:
    """An ``rows x cols`` (``"data"``, ``"model"``) mesh over the first
    ``rows * cols`` of ``jax.devices()``."""
    devices = jax.devices()
    need = rows * cols
    if len(devices) < need:
        raise SystemExit(
            f"grid {rows}x{cols} needs {need} devices, but JAX sees "
            f"{len(devices)} {devices[0].platform} device(s). On the CPU, "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={need}."
        )
    return jax.make_mesh((rows, cols), ("data", "model"), devices=devices[:need])
