"""Summed ``store.resolve`` span time over the rows those spans resolved."""

from layer_metrics_common import obs_spans_named


def read(readings):
    spans = obs_spans_named(readings, "store.resolve")
    rows = sum(int(s["attrs"].get("rows", 0)) for s in spans)
    if not rows:
        return None
    return sum(s["dur_ns"] for s in spans) * 1e-3 / rows
