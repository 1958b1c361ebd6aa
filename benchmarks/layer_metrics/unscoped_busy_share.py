"""Device self time of the ops that carry no ``photon.*`` scope in the
program's table, or are not in it, over device busy time, %: how complete
the attribution is."""

import layer_join


def read(readings):
    return layer_join.busy_share(readings, layer_join.UNSCOPED)
