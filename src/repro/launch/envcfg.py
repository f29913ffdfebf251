"""Reproducible-timing environment configuration + fingerprinting.

Kernel timings are only comparable when the numerical environment that
produced them is pinned: x64 mode changes every dtype default, the platform
pin changes which backend compiles, and XLA flags change the generated code.
This module does two things:

  * **configure** the environment for a timing run (x64 toggle, platform
    pin, host device count) — thin wrappers over ``jax.config`` in the style
    of the exemplar env-config helpers (SNIPPETS.md 1-3), callable only
    before JAX backends initialize where noted, and place the persistent
    compilation cache (``use_compile_cache``) so that every process of a
    checkout shares one;
  * **fingerprint** the environment (library versions, backend, device kind,
    x64 state, and the XLA/repro env vars that alter codegen) so timing
    artifacts can refuse to be reused under a different environment. The
    kernel autotuner (``repro.kernels.autotune``) stores this fingerprint in
    its cache and rejects stale caches on load.
"""
from __future__ import annotations

import hashlib
import json
import os
import platform
import sys
from pathlib import Path
from typing import Dict, Optional

import jax

# Env vars that change generated code or measured time; captured verbatim
# (unset vars are omitted so an empty and an absent var fingerprint alike).
CAPTURED_ENV_VARS = (
    "XLA_FLAGS",
    "JAX_ENABLE_X64",
    "JAX_PLATFORMS",
    "JAX_DEFAULT_DTYPE_BITS",
    "LD_PRELOAD",
    "REPRO_CACHE_MODE",
    "TF_CPP_MIN_LOG_LEVEL",
)


# ------------------------------------------------------------- configuration
def enable_x64(use_x64: bool = True) -> None:
    """Toggle 64-bit default precision (changes every timed kernel's dtype)."""
    jax.config.update("jax_enable_x64", bool(use_x64))


def set_platform(name: str) -> None:
    """Pin the JAX platform ("cpu" | "gpu" | "tpu"). Only effective before
    the first backend initialization of the process."""
    jax.config.update("jax_platform_name", name)


def set_host_device_count(n: int) -> None:
    """Force n XLA host devices (prepended to XLA_FLAGS). Must run before
    JAX initializes its backends; later calls are silently ineffective for
    the current process but still land in the fingerprint."""
    n = int(n)
    if n < 1:
        raise ValueError(f"need >= 1 host devices, got {n}")
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if not f.startswith("--xla_force_host_platform_device_count"))
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n} {flags}".strip())


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
    The default is fixed (found from this file, never a temp name or a pid):
    a cache that moves is never found again."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(Path(__file__).resolve().parents[3] / ".jax_cache"))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache at ``compile_cache_dir()``
    and return that directory. Call before the first compile. A set
    ``JAX_COMPILATION_CACHE_DIR`` is left alone: JAX reads it itself.

    Every program is cached, not only those over JAX's default one-second
    floor: the paged serving steps compile in about a second on a v5e host,
    and a fresh process would otherwise compile them again."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def configure_timing_env(*, x64: bool = False, platform_name: Optional[str] = None,
                         host_devices: Optional[int] = None) -> Dict[str, str]:
    """Apply a reproducible-timing configuration and return its fingerprint.

    The returned fingerprint reflects the environment AFTER configuration,
    so it is what a timing artifact produced under this call should record.
    """
    if host_devices is not None:
        set_host_device_count(host_devices)
    if platform_name is not None:
        set_platform(platform_name)
    enable_x64(x64)
    return env_fingerprint()


# -------------------------------------------------------------- fingerprint
def env_fingerprint() -> Dict[str, str]:
    """Stable description of everything that can change a kernel timing.

    Keys are sorted strings so the fingerprint JSON-serializes canonically;
    ``fingerprint_digest`` hashes exactly this dict.
    """
    try:
        import jaxlib
        jaxlib_version = getattr(jaxlib, "__version__", "unknown")
    except ImportError:                      # pragma: no cover - jaxlib ships with jax
        jaxlib_version = "missing"
    import numpy as np

    devices = jax.devices()
    fp = {
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "none",
        "device_count": str(len(devices)),
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "machine": platform.machine(),
        "numpy": np.__version__,
        "python": ".".join(map(str, sys.version_info[:3])),
        "system": platform.system(),
        "x64": str(bool(jax.config.jax_enable_x64)),
    }
    for var in CAPTURED_ENV_VARS:
        val = os.environ.get(var)
        if val:
            fp[f"env:{var}"] = val
    return dict(sorted(fp.items()))


def fingerprint_digest(fp: Optional[Dict[str, str]] = None) -> str:
    """Short stable hash of a fingerprint (current environment's if None)."""
    if fp is None:
        fp = env_fingerprint()
    blob = json.dumps(fp, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]
