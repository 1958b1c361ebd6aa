"""The join the layer readers share: the program's op-to-layer tables x the
reduced trace's ``ops_self`` -> seconds per layer.

The descent program names its layers with ``photon.*`` scopes and records,
per traced process, which instruction of its executable carries which scope
path (``photon_ml_tpu.obs.trace``: ``device_scope``, ``hlo_op_table``, kept
by the tracer).  ``trace_reduce`` keys device self time by instruction name
(``fusion.71``).  Joined here, by name.  The readers take the table from
the tracer directly: the traffic kinds hand them no ``obs_spans``.

Every function returns None (or nothing) where there is nothing to read: no
device trace (a CPU dry run), or a program that records no table and no
such span (the parent of the PR that added them).

Known limits, not engineered around: a fusion is charged to the scope of
the instruction that gave it its metadata, so an add fused into a gather
counts with the gather; ``ops_self`` merges instruction names over every
program in the slice, so the microseconds of ``jit_finite`` and the PRNG
seed land on whatever the main program calls ``fusion`` or ``fusion.1``.
"""

from __future__ import annotations

# obs.trace.SCOPE_PREFIX, spelled out: these readers also run against a
# program that has no such name (the parent of the PR that added the scopes)
PREFIX = "photon."
UPDATE = "update."
UNSCOPED = "unscoped"


def op_table() -> dict | None:
    """{instruction name: op_name path} over the programs the tracer holds
    a table of, or None."""
    from photon_ml_tpu import obs

    tables = getattr(obs.get_tracer(), "device_tables", None)
    if tables is None:
        return None
    merged = {}
    for table in tables().values():
        merged.update(table)
    return merged or None


def layer_of(path: str) -> str:
    """``.../photon.update.per_user/photon.rescore/gather`` -> ``rescore``:
    the innermost scope; ``update.<cid>`` only where no layer is inside
    it; UNSCOPED where the path holds no scope."""
    layer = UNSCOPED
    for part in path.split("/"):
        if part.startswith(PREFIX):
            name = part[len(PREFIX):]
            if layer == UNSCOPED or not name.startswith(UPDATE):
                layer = name
    return layer


def coordinate_of(path: str) -> str | None:
    """The ``<cid>`` of the ``photon.update.<cid>`` the op sits under."""
    for part in path.split("/"):
        if part.startswith(PREFIX + UPDATE):
            return part[len(PREFIX + UPDATE):]
    return None


def seconds_by(readings: dict, key=layer_of) -> dict | None:
    """{key(path): device self seconds} over the traced slice; an
    instruction the table lacks counts as UNSCOPED."""
    profile, table = readings["profile"], op_table()
    if not profile or table is None:
        return None
    out = {}
    for name, (own_ns, _calls) in profile["ops_self"].items():
        k = key(table[name]) if name in table else UNSCOPED
        out[k] = out.get(k, 0.0) + own_ns * 1e-9
    return out


def busy_share(readings: dict, *layers: str) -> float | None:
    """Self time of the ops whose layer is one of ``layers`` (or starts
    with one that ends in "."), over device busy time, %."""
    seconds = seconds_by(readings)
    profile = readings["profile"]
    if seconds is None or profile["busy_s"] <= 0:
        return None
    hit = sum(s for layer, s in seconds.items()
              if any(layer == want or (want.endswith(".")
                                       and layer.startswith(want))
                     for want in layers))
    return 100.0 * hit / (profile["busy_s"] * profile["chips"])


def solver_loop_calls(readings: dict) -> dict | None:
    """{(cid, bucket scope): trips}: for every ``photon.entity_solve.b<n>``
    scope, how often the loop directly below it ran in the slice, the
    solver's outer loop: the MOST COMMON call count among the ops under
    its ``while/body`` with no further ``while`` in their path.  That
    leaves out what runs in an inner loop (the line search, the two-loop
    recursion) and what carries an inner ``while``'s own path: the
    compiler gives that to instructions it makes INSIDE that loop too.
    The most common and not the largest: an op under a ``cond`` runs less
    often, and a name that another program of the slice also uses (see
    the known limits) counts that program's calls on top."""
    profile, table = readings["profile"], op_table()
    if not profile or table is None:
        return None
    counts = {}
    for name, (_own, calls) in profile["ops_self"].items():
        parts = table.get(name, "").split("/")
        scope = next((i for i, p in enumerate(parts)
                      if p.startswith(PREFIX + "entity_solve.")), None)
        if scope is None:
            continue
        below = parts[scope + 1:]
        loop = next((i for i, pair in enumerate(zip(below, below[1:]))
                     if pair == ("while", "body")), None)
        if loop is None or "while" in below[loop + 2:]:
            continue
        seen = counts.setdefault((coordinate_of(table[name]), parts[scope]),
                                 {})
        seen[calls] = seen.get(calls, 0) + 1
    return {k: max(seen, key=lambda calls: (seen[calls], calls))
            for k, seen in counts.items()} or None


def program_spans(name: str) -> list:
    """The tracer's complete spans of that name (the program's own)."""
    from photon_ml_tpu import obs

    return [r for r in obs.get_tracer().records()
            if r["ph"] == "X" and r["name"] == name]


def span_seconds(name: str) -> float | None:
    spans = program_spans(name)
    if not spans:
        return None
    return sum(r["dur_ns"] for r in spans) * 1e-9
