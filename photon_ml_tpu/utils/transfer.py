"""Host->device placement of design arrays, probe-accounted.

``device_put_counted`` is the one place a coordinate's design arrays cross
to the device: dtype narrowing happens on the HOST, so the transfer and the
resident array are storage-width from the start (an on-device cast would
hold both widths in HBM at once), and every upload is counted by the
runtime probe.

The streaming feed assembles its output ON device instead: each batch is
written into a preallocated buffer by a DONATED ``lax.dynamic_update_slice``
(``stream_update``), so the device-memory peak is output + the batches in
flight — not output + all batches as a ``jnp.concatenate`` would give.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_ml_tpu.obs import get_probe
from photon_ml_tpu.obs import trace as _trace

# Module-level jit: one wrapper, one compile per (shape-pair).  The start
# index is traced, so successive offsets reuse the compiled program; only a
# ragged last batch adds a second compile.
_UPDATE = jax.jit(lax.dynamic_update_slice, donate_argnums=0)


def _update_at(out: jax.Array, part: jax.Array, lo: int) -> jax.Array:
    """Donated write of ``part`` at row ``lo``: reuses ``out``'s buffer, so
    assembling N parts never holds more than output + one part on device."""
    start = (lo,) + (0,) * (out.ndim - 1)
    # photonlint: disable=donation-after-use -- documented consuming
    # contract: the caller owns ``out`` and immediately rebinds it
    # (out = _update_at(out, ...)); donating the caller's buffer is the
    # point — the device peak stays output + one part
    return _UPDATE(out, part, start)


def stream_device_put(arr: np.ndarray, dtype=None) -> jax.Array:
    """Non-blocking upload of one fixed-shape stream-feed batch.

    The streaming data plane's upload primitive: it never blocks
    (double-buffering wants the transfer in flight while the decode pool
    fills the next batch).  Every upload is probe-accounted under
    ``site="stream_feed"``.
    """
    arr = np.asarray(arr, dtype)
    get_probe().record_transfer(arr.nbytes, "h2d", site="stream_feed")
    with _trace.span("stream.upload", bytes=int(arr.nbytes)):
        return jnp.asarray(arr)


def stream_update(out: jax.Array, part: jax.Array, lo: int,
                  rows: int) -> jax.Array:
    """Donated write of a (possibly padded) stream batch at row ``lo``.

    ``rows`` is the batch's VALID row count; pow2-padded batches are sliced
    to it first because ``lax.dynamic_update_slice`` CLAMPS out-of-range
    start indices — writing a padded tail block at a clamped start would
    silently overwrite the rows before it.  The slice costs one extra
    ``_UPDATE`` compile for the single tail shape; every full batch reuses
    the one program (start index is traced).
    """
    if rows != part.shape[0]:
        part = part[:rows]
    # photonlint: disable=donation-after-use -- documented consuming
    # contract: DeviceFeed owns ``out`` and immediately rebinds it
    # (self._out[gid] = stream_update(self._out[gid], ...)); donating keeps
    # the device peak at output + in-flight batches across the whole stream
    return _update_at(out, part, lo)


def device_put_counted(arr, dtype=None) -> jax.Array:
    """``jnp.asarray(np.asarray(arr, dtype))`` with probe accounting.

    A device-resident ``arr`` (a streamed shard, or one upload shared by
    several coordinates) is never round-tripped through the host: it passes
    straight through, and a dtype mismatch casts on device — transiently
    double-resident, so callers that care about storage narrowing should
    upload narrowed host bytes instead.
    """
    if isinstance(arr, jax.Array):
        want = jnp.dtype(dtype) if dtype is not None else arr.dtype
        return arr if arr.dtype == want else arr.astype(want)
    arr = np.asarray(arr, dtype)
    get_probe().record_transfer(arr.nbytes, "h2d", site="device_put")
    with _trace.span("transfer.device_put", bytes=int(arr.nbytes)):
        return jnp.asarray(arr)
