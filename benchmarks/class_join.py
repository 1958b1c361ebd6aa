"""What the readers of the capacity-class metrics share: the program's
``coord.bucket`` spans, of which one per random-effect coordinate holds what
the bucketer made of the rows per entity.

Empty where the program records no such attributes (the parent of the PR
that added them): the metric is then left out."""

from __future__ import annotations

import layer_join


def classes() -> dict:
    """{coordinate id: the attributes of its ``coord.bucket`` span}:
    ``classes``, ``capacities`` and ``lanes`` per class, ``slots``,
    ``active_rows``, ``capped_entities``, ``passive_rows``."""
    return {s["attrs"]["coordinate"]: s["attrs"]
            for s in layer_join.program_spans("coord.bucket")
            if "classes" in s["attrs"]}
