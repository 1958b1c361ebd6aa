"""Iterations the per-entity solver RAN per update (a vmapped loop runs to
its slowest lane), mean over the buckets: trips of each bucket's outer
solver loop in the slice (layer_join.solver_loop_calls) over the updates
the slice holds, fits x sweeps."""

import layer_join


def read(readings):
    trips = layer_join.solver_loop_calls(readings)
    fits = readings["measured"].get("slice_fits")
    if not trips or not fits:
        return None
    updates = fits * int(readings["config"]["sweeps"])
    return sum(trips.values()) / len(trips) / updates
