"""Device self time of the ops under ``photon.validate.*`` (held-out scoring
after each update, the held-out loss) and ``photon.evaluate.*`` (the metric
suite at each sweep boundary) over device busy time, %.  None where the
program names no such scope (it keeps no table of its validated program)."""

import layer_join


def read(readings):
    seconds = layer_join.seconds_by(readings)
    if not seconds or not any(layer.startswith(("validate.", "evaluate."))
                              for layer in seconds):
        return None
    return layer_join.busy_share(readings, "validate.", "evaluate.")
