"""Device time of ``soa_newton_step`` over device busy time, %."""

import trace_reduce


def read(readings):
    p = readings["profile"]
    if not p or p["busy_s"] <= 0:
        return None
    seconds, _ = trace_reduce.time_of(p["ops_self"], "soa_newton_step")
    return 100.0 * seconds / (p["busy_s"] * p["chips"])
