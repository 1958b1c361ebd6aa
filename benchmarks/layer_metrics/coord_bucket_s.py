"""Seconds under the program's ``coord.bucket`` spans: host grouping and
the Python packing loops of every coordinate built."""

import layer_join


def read(readings):
    return layer_join.span_seconds("coord.bucket")
