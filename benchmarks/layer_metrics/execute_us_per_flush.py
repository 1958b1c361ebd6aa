"""Mean ``serve.execute`` span time, microseconds."""

from layer_metrics_common import obs_spans_named


def read(readings):
    spans = obs_spans_named(readings, "serve.execute")
    if not spans:
        return None
    return sum(s["dur_ns"] for s in spans) * 1e-3 / len(spans)
