"""Device self time of the ops under ``photon.backproject`` (compact lanes
scattered into full-dimension rows, inside ``photon.publish``) over device
busy time, %."""

import layer_join


def read(readings):
    seconds, p = layer_join.seconds_by(readings), readings["profile"]
    if seconds is None or "backproject" not in seconds or p["busy_s"] <= 0:
        return None
    return 100.0 * seconds["backproject"] / (p["busy_s"] * p["chips"])
