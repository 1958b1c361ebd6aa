"""The roofline of the exchange, %: the bytes a chip MUST send in the fits
of the traced slice (``exchange_model.must_send_bytes``, from the
configuration's shapes) over the collective seconds a chip spent in them x
the chip's interconnect peak (``peaks.json``).  None where the slice ran no
collective or the program records no ``descent.exchange`` span."""

import exchange_model
import roofline
import trace_reduce


def read(readings):
    p, fits = readings["profile"], readings["measured"].get("slice_fits")
    if not p or not fits:
        return None
    seconds = exchange_model.collective_seconds(p)
    if not seconds:
        return None
    seconds /= p["chips"]
    # the fixed effect's evaluations: the kernel calls of the slice, a chip
    _, calls = trace_reduce.time_of(p["ops_self"], "fused_glm_value_grad",
                                    "fused_glm_hvp")
    must = exchange_model.must_send_bytes(
        readings["config"], readings["chips"],
        exchange_model.rows_of(readings["config"]),
        fixed_evaluations=calls / p["chips"] / fits)
    peak = roofline.peaks_for(readings["device"]["kind"])["ici_bits_per_s"] / 8
    return 100.0 * sum(must.values()) * fits / (seconds * peak)
