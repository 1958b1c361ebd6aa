"""Seconds under the program's ``coord.upload`` spans: every coordinate's
arrays on their way to the device."""

import layer_join


def read(readings):
    return layer_join.span_seconds("coord.upload")
