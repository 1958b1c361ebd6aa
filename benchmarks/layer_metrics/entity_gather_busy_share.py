"""Device self time of the ops under ``photon.entity_gather`` (offsets
gathered into lanes before a per-entity solve) over device busy time, %."""

import layer_join


def read(readings):
    return layer_join.busy_share(readings, "entity_gather")
