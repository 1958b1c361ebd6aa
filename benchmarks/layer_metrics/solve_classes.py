"""Capacity classes of all random-effect coordinates: the unrolled
per-class solves of the descent program (``coord.bucket``'s ``classes``)."""

import class_join


def read(readings):
    found = class_join.classes()
    return sum(c["classes"] for c in found.values()) if found else None
