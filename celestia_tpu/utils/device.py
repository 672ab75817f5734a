"""Device-backend facts and the persistent compile-cache placement.

Everything here runs in the process that does the device work: a chip
belongs to one process at a time, so nothing probes the backend in a
child.  A backend that fails to initialize raises here — it is never
read as "host-only".
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# the one fixed in-checkout cache path (listed in .gitignore): the path
# is part of what a cached executable is found by, so it never moves
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_host_regime: Optional[bool] = None


def host_regime() -> bool:
    """True when this process's default jax backend is the host CPU.

    The host-regime fast paths (da/dah.py) route the DA pipeline through
    the pooled native C++ legs instead of compiling XLA CPU programs
    (minutes at k=128).  Cached: the default backend cannot change within
    a process.  The first call initializes the backend, and a backend
    that fails to come up raises here rather than passing for the CPU."""
    global _host_regime
    if _host_regime is None:
        import jax

        _host_regime = jax.default_backend() == "cpu"
    return _host_regime


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is set here.  Otherwise the cache lives at the one fixed
    path inside the checkout (:data:`DEFAULT_CACHE_DIR`).  Call before
    the first compile."""
    env = os.environ.get(ENV_CACHE_DIR, "").strip()
    if env:
        return env
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    # the cache binds its directory at the first compile: drop a binding
    # an earlier compile of this process made without one
    compilation_cache.reset_cache()
    return str(DEFAULT_CACHE_DIR)


def force_host_devices_env(env: dict, n: int) -> dict:
    """Prepare ``env`` (in place; also returned) so a CHILD process sees
    an n-device virtual CPU mesh: pins JAX_PLATFORMS=cpu and sets or
    REPLACES ``--xla_force_host_platform_device_count`` in XLA_FLAGS —
    the flag only takes effect before jax initialises, which is why
    every user of it starts a fresh CPU-only process (dryrun_multichip,
    the mesh smoke; this is the one shared copy of that setup)."""
    import re

    env["JAX_PLATFORMS"] = "cpu"
    flag = f"--xla_force_host_platform_device_count={int(n)}"
    xf = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in xf:
        xf = re.sub(
            r"--xla_force_host_platform_device_count=\d+", flag, xf
        )
    else:
        xf = (xf + " " + flag).strip()
    env["XLA_FLAGS"] = xf
    return env
