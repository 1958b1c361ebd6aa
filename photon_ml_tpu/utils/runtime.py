"""Process start-up shared by the command-line drivers, the benchmark and
chip_smoke.py: place the compile cache, then say once which device the
process actually runs on.

JAX falls back to the CPU when no accelerator answers (it logs a libtpu
error and carries on), so a run that does not name its device can pass for
a chip run.  Every entry point therefore logs platform, device kind and
device count at start, and the measuring ones (benchmarks/run.py,
chip_smoke.py) refuse a platform they were not asked for.
"""

from __future__ import annotations

import logging
from typing import Dict, Optional

from photon_ml_tpu.utils.compile_cache import enable_compilation_cache


def device_summary() -> Dict[str, object]:
    """The device as JAX reports it — the fields every result line names."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def init_runtime(logger: Optional[logging.Logger] = None) -> Dict[str, object]:
    """Enable the persistent compile cache and log the one start-up line;
    returns ``device_summary()``.  Initialises the JAX backend: a process
    that must call ``jax.distributed.initialize`` first does that before
    this."""
    cache_dir = enable_compilation_cache()
    dev = device_summary()
    (logger or logging.getLogger(__name__)).info(
        "platform %s, device_kind %s, %d device(s); compile cache %s",
        dev["platform"], dev["kind"], dev["count"], cache_dir or "disabled")
    return dev
