"""Process-level JAX set-up shared by the entry points.

Called from ``main()`` of each entry point, never at import: importing a
module must not change how the process compiles or which device it takes.

- ``enable_compile_cache`` keeps JAX's persistent compilation cache at a
  fixed path, so a second run of the same program skips its compiles.
- ``pin_cpu`` holds a host-only tool to the CPU backend.  A chip belongs
  to one process at a time; a tool that only needs host devices must not
  take it from (or wait on) the process that serves.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_ENV", "enable_compile_cache", "pin_cpu",
           "checkout_root"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_root() -> str:
    """The repository checkout this package was imported from."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``$JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and
    nothing else is configured.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    what a later run looks up.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = os.path.join(checkout_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pin_cpu(tool: str) -> None:
    """Hold this process to the CPU backend, or exit naming the platform.

    Must run before the process's first device query: once a backend is
    up, the platform setting no longer applies and the check below
    refuses to go on.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"        # for child processes
    jax.config.update("jax_platforms", "cpu")
    found = jax.default_backend()
    if found != "cpu":
        raise SystemExit(f"{tool} runs on host CPU devices only; this "
                         f"process already holds platform {found!r}")
