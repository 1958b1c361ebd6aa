"""Device self time of the ops under ``photon.entity_solve.b<n>`` (every
bucket of every random effect) over device busy time, %."""

import layer_join


def read(readings):
    return layer_join.busy_share(readings, "entity_solve.")
