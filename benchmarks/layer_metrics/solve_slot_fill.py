"""Active rows over the slots (lanes x capacity) of every capacity class of
every random-effect coordinate, %."""

import class_join


def read(readings):
    found = class_join.classes().values()
    slots = sum(c["slots"] for c in found)
    return 100.0 * sum(c["active_rows"] for c in found) / slots if slots else None
