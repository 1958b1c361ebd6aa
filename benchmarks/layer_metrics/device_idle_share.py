"""1 - busy/window of the traced slice, in percent."""


def read(readings):
    p = readings["profile"]
    if not p or p["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
