"""Device self time of the ops under ``photon.rescore`` (a coordinate's
full-sample scores after its update) over device busy time, %."""

import layer_join


def read(readings):
    return layer_join.busy_share(readings, "rescore")
