"""Programs of set-up that were COMPILED: the ``jax.compile`` spans before
the window whose ``cache`` is not ``hit`` and that took over a second (the
tiny eager programs are compiled in every process and say nothing).  0 on
a checkout's second run; the number that tells ``first_setup_s`` from
``setup_s``.  None where the program records no such span."""

OVER_NS = 1_000_000_000


def read(readings):
    shared = readings["catalog"].module("layer_metrics", "setup_trace_s")
    if not shared.setup_spans(readings, "jax.trace"):
        return None  # the listener was not there: nothing to say
    return sum(r["attrs"].get("cache") != "hit" and r["dur_ns"] > OVER_NS
               for r in shared.setup_spans(readings, "jax.compile"))
