"""Achieved HBM bytes/s of the fixed-effect kernels on a chip's SHARD of the
design over the chip's peak, %: bytes one call must move over a shard
(roofline.fused_glm_call on the source's rows over the chips, from the
config's shapes) x the calls of the slice, over those calls' device time
(both summed over the chips, so the share is a chip's)."""

import exchange_model
import roofline
import trace_reduce

KERNELS = ("fused_glm_value_grad", "fused_glm_hvp")


def read(readings):
    p = readings["profile"]
    if not p:
        return None
    seconds, calls = trace_reduce.time_of(p["ops_self"], *KERNELS)
    if seconds <= 0:
        return None
    config = readings["config"]
    fixed = next(c for c in config["coordinates"] if c["kind"] == "fixed")
    shard_rows = -(-exchange_model.rows_of(config) // readings["chips"])
    need = roofline.fused_glm_call(shard_rows, int(fixed["dim"]),
                                   fixed.get("storage_dtype"))
    peak = roofline.peaks_for(readings["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need["bytes"] * calls / seconds / peak
