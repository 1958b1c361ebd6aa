"""Share of the per-entity solves' lane-iterations that ran past their
lane's own convergence, weighted by capacity, %: a class's vmapped solve
runs ``lanes x trips`` lane-iterations where its lanes needed the sum of
their own.  From the program's per-class iteration outputs, as a traced
fit records them in ``descent.solve_iterations``, over every traced fit's
updates of every random effect."""

import class_join
import layer_join


def read(readings):
    by_coordinate = class_join.classes()
    needed = run = 0
    for span in layer_join.program_spans("descent.solve_iterations"):
        a = span["attrs"]
        for cid, sums, trips in zip(a["coordinates"], a["lane_iterations"],
                                    a["trips"]):
            c = by_coordinate.get(cid)
            if c is None:  # a fixed effect: one problem, no lanes
                continue
            for k, (cap, lanes) in enumerate(zip(c["capacities"],
                                                 c["lanes"])):
                needed += cap * sum(update[k] for update in sums)
                run += cap * lanes * sum(update[k] for update in trips)
    return 100.0 * (1.0 - needed / run) if run else None
