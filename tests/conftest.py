"""Test harness: an 8-device virtual CPU mesh, float64 on.

Mirrors the reference's test strategy (SURVEY.md §4): the reference exercises
distributed code on Spark local[*] in one JVM; we exercise SPMD code on
xla_force_host_platform_device_count=8 virtual CPU devices in one process.
float64 is enabled so parity tests against scipy/numpy are tight; library code
is dtype-agnostic (TPU runs follow input dtypes, normally bf16/f32).

JAX_PLATFORMS and XLA_FLAGS are plain environment variables, read when the
backend first initialises; setting them here (before jax is imported) also
hands them to every subprocess a test starts, so no child ever reaches for
a chip.  x64 is set in-process only: child CLIs keep their float32 default.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # even where the ambient default is a TPU
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs


_MULTIPROCESS_VERDICT = None  # session memo: (supported: bool, reason: str)

_MULTIPROCESS_PROBE = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, port = int(sys.argv[1]), sys.argv[2]
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=2, process_id=pid)
import jax.numpy as jnp
from jax.experimental import multihost_utils
multihost_utils.process_allgather(jnp.ones(1))  # first cross-process op
"""


def multiprocess_backend_supported():
    """Probe (once per session) whether the backend can run CROSS-PROCESS
    computations: two jax.distributed subprocesses attempt one collective.
    ``jax.distributed.initialize`` itself succeeds everywhere — the CPU
    backend only fails at the first multi-process computation
    ("Multiprocess computations aren't implemented on the CPU backend"),
    so the probe must execute a collective, not just form the cluster.
    Returns ``(supported, reason)``."""
    global _MULTIPROCESS_VERDICT
    if _MULTIPROCESS_VERDICT is not None:
        return _MULTIPROCESS_VERDICT
    import socket
    import subprocess
    import sys
    import tempfile

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env.pop("PYTEST_CURRENT_TEST", None)
    with tempfile.NamedTemporaryFile("w", suffix="_mp_probe.py",
                                     delete=False) as f:
        f.write(_MULTIPROCESS_PROBE)
        probe = f.name
    try:
        procs = [subprocess.Popen(
            [sys.executable, probe, str(pid), port], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True)
            for pid in range(2)]
        try:
            errs = [p.communicate(timeout=120)[1] for p in procs]
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
                p.communicate()
            _MULTIPROCESS_VERDICT = (False, "multi-process probe timed out")
            return _MULTIPROCESS_VERDICT
        if all(p.returncode == 0 for p in procs):
            _MULTIPROCESS_VERDICT = (True, "")
        else:
            tail = next((e for p, e in zip(procs, errs) if p.returncode),
                        "").strip().splitlines()
            _MULTIPROCESS_VERDICT = (
                False, tail[-1] if tail else "probe worker failed")
        return _MULTIPROCESS_VERDICT
    finally:
        os.unlink(probe)


def require_multiprocess_backend():
    """Skip the calling test when the runtime cannot execute true
    multi-process computations (e.g. the CPU backend, which forms the
    jax.distributed cluster but rejects every cross-process op)."""
    supported, reason = multiprocess_backend_supported()
    if not supported:
        pytest.skip("multi-process computations unavailable on this "
                    f"backend: {reason}")


@pytest.fixture()
def rng():
    import numpy as np

    return np.random.default_rng(20260729)


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compile cache in a fresh directory, every program
    cached; the process's settings restored afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    flags = {"jax_compilation_cache_dir": str(tmp_path / "xla"),
             "jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": -1,
             "jax_enable_compilation_cache": True}
    before = {k: getattr(jax.config, k) for k in flags}
    for k, v in flags.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
