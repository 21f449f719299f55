"""Process-level JAX setup shared by the entry points.

Both helpers run before the first backend call and never initialize a
backend themselves: one process at a time may hold a TPU, so a parent
that touched the backend would hold the chip a child needs.

* :func:`enable_compile_cache` — the persistent XLA compilation cache.
  ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it;
  otherwise the cache lives at a fixed ``.jax_cache/`` in the repo root
  (the path is part of the cache key, so it never moves).
* :func:`host_mesh_env` / :func:`in_host_mesh` — the environment of a
  CPU host-mesh child process (``JAX_PLATFORMS=cpu`` plus forced host
  devices), and the check a process makes, from its environment alone,
  of whether it already is one.
"""
from __future__ import annotations

import os
import re
from typing import Dict

__all__ = ["CACHE_DIR", "enable_compile_cache", "host_mesh_env",
           "in_host_mesh"]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

_DEVCOUNT_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def host_mesh_env(ndev: int, *, x64: bool = False) -> Dict[str, str]:
    """A copy of this process's environment for a child that runs on a
    CPU host mesh of ``ndev`` forced devices (``src`` and the repo root
    on its ``PYTHONPATH``)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = _DEVCOUNT_RE.sub("", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={ndev}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), ROOT,
                    env.get("PYTHONPATH", "")) if p)
    if x64:
        env["JAX_ENABLE_X64"] = "1"
    return env


def in_host_mesh(ndev: int) -> bool:
    """Whether this process runs on a CPU host mesh of at least ``ndev``
    devices, judged from its environment (no backend is initialized)."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return False
    m = _DEVCOUNT_RE.search(os.environ.get("XLA_FLAGS", ""))
    return (int(m.group(1)) if m else 1) >= ndev
