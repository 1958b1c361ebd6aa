"""Device self time of the ops under ``photon.rescore`` of the coordinates
whose full-sample layout is sparse, over device busy time, %.  None where
the program records no such layout."""

import sparse_rescore_model


def read(readings):
    seconds = sparse_rescore_model.rescore_seconds(readings)
    p = readings["profile"]
    if seconds is None or p["busy_s"] <= 0:
        return None
    return 100.0 * seconds / (p["busy_s"] * p["chips"])
