"""Persistent XLA compilation cache.

TPU first-compiles of the while_loop-heavy solvers are tens of seconds; the
persistent cache makes every LATER process (reruns, scoring after training,
benchmarks) hit compiled binaries instead.  The reference's analog is the
JVM warming Spark executors once per application — here the warmth survives
across processes on disk.

One variable places the cache: where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX itself keeps the cache there and this module sets no directory in code
(the path is part of the cache key's world — a directory chosen here would
hide the one the machine came with).  Unset, the cache lives at the fixed
``<checkout>/.xla_cache/<cpu tag>``.  JAX's own
``JAX_ENABLE_COMPILATION_CACHE=false`` turns it off.
"""

from __future__ import annotations

import os
from typing import Optional


def _host_tag() -> str:
    """Short stable id of THIS machine's CPU capabilities.

    XLA:CPU AOT executables bake target-machine features; loading a cache
    written on a different host warns "+prefer-no-scatter ... not supported
    on the host machine ... could lead to execution errors such as SIGILL"
    (observed when the build environment migrated between rounds).  Keying
    the cache dir by a hash of the cpuinfo flags gives each machine type its
    own cache instead of sharing stale foreign binaries."""
    import hashlib

    try:
        with open("/proc/cpuinfo") as f:
            for ln in f:
                # x86 writes "flags", aarch64 writes "Features"
                if ln.startswith(("flags", "Features")):
                    return hashlib.sha256(
                        " ".join(sorted(ln.split()[2:])).encode()
                    ).hexdigest()[:10]
    except OSError:
        pass
    import platform

    # machine() is never empty ("x86_64"/"arm64"); processor() often is —
    # hash both so hosts without a parseable cpuinfo at least split by
    # architecture instead of silently sharing one tag
    return hashlib.sha256(
        f"{platform.machine()}|{platform.processor()}".encode()
    ).hexdigest()[:10]


def _default_dir() -> str:
    # Source checkout: repo-root .xla_cache (the package's grandparent holds
    # the repo's own files).  Installed package: user cache dir — the
    # grandparent is site-packages' parent, which must not be littered.
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    if os.path.exists(os.path.join(root, "photon_ml_tpu", "__init__.py")) \
            and not os.path.basename(root).endswith("-packages") \
            and os.access(root, os.W_OK):
        return os.path.join(root, ".xla_cache", _host_tag())
    return os.path.join(os.path.expanduser("~"), ".cache", "photon_ml_tpu",
                        "xla", _host_tag())


def enable_compilation_cache() -> Optional[str]:
    """Turn on jax's persistent compilation cache; returns the directory in
    use (None when JAX's own switch has it disabled).  Safe to call more
    than once.  A directory that cannot be created raises: a process that
    silently runs uncached pays every first-compile again, and on the chip
    that is minutes.

    Cache residency reports into the observability registry (gauge
    ``xla_compile_cache_enabled`` + a trace instant), next to the
    ``jax_compiles_total`` counters a missing cache inflates."""
    import jax

    from photon_ml_tpu.obs import get_probe

    if not jax.config.jax_enable_compilation_cache:
        get_probe().record_compile_cache(False)
        return None
    # cache everything that took noticeable compile time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        get_probe().record_compile_cache(True, placed)
        return placed
    cache_dir = _default_dir()
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    get_probe().record_compile_cache(True, cache_dir)
    return cache_dir
