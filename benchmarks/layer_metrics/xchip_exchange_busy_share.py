"""Self time of the instructions under ``photon.exchange.*`` (collectives
and the chip-local gathers and scatters that feed them) over device busy
time x chips, %.  None where the program names no exchange."""

import exchange_model
import layer_join


def read(readings):
    seconds = layer_join.seconds_by(
        readings, lambda path: exchange_model.exchange_kind(path))
    if seconds is None or readings["profile"]["busy_s"] <= 0:
        return None
    hit = [s for kind, s in seconds.items()
           if kind not in (None, layer_join.UNSCOPED)]
    p = readings["profile"]
    return 100.0 * sum(hit) / (p["busy_s"] * p["chips"]) if hit else None
