"""The sparse rescore's share of its roofline, %: the bytes its calls in
the traced slice MUST move (``sparse_rescore_model.rescore_call``, from the
rows and the row width) over the seconds under its ``photon.rescore`` x the
chip's peak HBM bytes/s.  None where the slice ran no such rescore."""

import roofline
import sparse_rescore_model


def read(readings):
    seconds = sparse_rescore_model.rescore_seconds(readings)
    fits, rows = (readings["measured"].get(k) for k in ("slice_fits", "rows"))
    if not seconds or not fits or not rows:
        return None
    widths = [a["row_width"] for a in
              sparse_rescore_model.sparse_coordinates().values()
              if "row_width" in a]
    if not widths:
        return None
    calls = fits * int(readings["config"]["sweeps"])
    need = sum(sparse_rescore_model.rescore_call(rows, k)["bytes"]
               for k in widths)
    peak = roofline.peaks_for(readings["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need * calls / seconds / peak
