"""Device self time of the ops under ``photon.fixed_solve`` over device
busy time, %."""

import layer_join


def read(readings):
    return layer_join.busy_share(readings, "fixed_solve")
