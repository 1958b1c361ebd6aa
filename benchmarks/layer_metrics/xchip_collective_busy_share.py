"""Self time of the collective operations over device busy time x chips, %.
None where the slice ran none (one chip) or the program does not say which
of its instructions are collectives (no ``descent.exchange`` span)."""

import exchange_model


def read(readings):
    p = readings["profile"]
    if not p or p["busy_s"] <= 0:
        return None
    seconds = exchange_model.collective_seconds(p)
    return 100.0 * seconds / (p["busy_s"] * p["chips"]) if seconds else None
