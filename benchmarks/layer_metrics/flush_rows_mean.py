"""Rows per launched micro-batch: ``ServingMetrics`` ``scored_samples``
over ``batches``, window delta."""


def read(readings):
    c = readings["counters"]
    if not c.get("batches"):
        return None
    return c["scored_samples"] / c["batches"]
