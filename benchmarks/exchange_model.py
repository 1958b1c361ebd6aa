"""What a GLMix fit MUST move between chips, from the configuration's shapes
alone, and how the readers of the cross-chip metrics find what did move.

Kept with the benchmark: the bytes a roofline share rests on are the
yardstick's, not the program's.  The program says what ITS exchanges send
(span ``descent.exchange``: an all-gather sends each chip's shard to every
other chip, a psum twice that); tests/test_mesh_exchange.py holds the two to
each other: the program's vectors are these, times the chips.

The model: the rows of a fit lie over the chips in sample order, a random
effect's entities over the same chips.  An update of a random effect needs
each row's residual at its entity's chip and the row's new score back.
With entities spread evenly, (chips - 1) / chips of a chip's n / chips rows
have their entity elsewhere: that many float32 leave the chip each way, per
random effect and sweep, whatever implements it.  The fixed effect's
objective evaluations each all-reduce (value, gradient [d], residual sum): a
ring sends 2 (chips - 1) / chips of it.  Publishing coefficients to every
chip is a choice of the implementation (the scores could be computed where
the entity lives), so it is not in the model; it is in the time.
"""

from __future__ import annotations

SCOPE = "photon.exchange."


def rows_of(config: dict) -> int:
    """The fit's rows: the source's (``reduced: []``)."""
    return int(config["source_rows"])


def must_send_bytes(config: dict, chips: int, rows: int,
                    fixed_evaluations: float = 0.0) -> dict:
    """{kind: bytes ONE chip must send in ONE fit} (float32)."""
    sweeps = int(config["sweeps"])
    away = (chips - 1) / chips * -(-rows // chips) * 4
    effects = [c for c in config["coordinates"] if c["kind"] == "random"]
    fixed = [c for c in config["coordinates"] if c["kind"] == "fixed"]
    psum = sum(2 * (chips - 1) / chips * (int(c["dim"]) + 2) * 4
               for c in fixed)
    return {"offsets": sweeps * len(effects) * away,
            "scores": sweeps * len(effects) * away,
            "psum": fixed_evaluations * psum}


def exchange_kind(path: str) -> str | None:
    """``.../photon.exchange.offsets/photon.entity_gather/gather`` ->
    ``offsets``: the exchange an instruction sits under, wherever in its
    path; None outside every exchange."""
    for part in path.split("/"):
        if part.startswith(SCOPE):
            return part[len(SCOPE):]
    return None


def collective_seconds(profile: dict) -> float | None:
    """Self seconds of the main program's collective operations, summed
    over the chips: the instructions the program's ``descent.exchange``
    span names as collectives (by opcode, from its own executable: their
    names do not tell), their ``-start`` and ``-done`` halves among them.
    None where the program records no such span."""
    import layer_join

    spans = [s["attrs"] for s in layer_join.program_spans("descent.exchange")
             if "collectives" in s["attrs"]]
    if not spans:
        return None
    names = spans[-1]["collectives"]
    return sum(own for name, (own, _calls) in profile["ops_self"].items()
               if name in names) * 1e-9
