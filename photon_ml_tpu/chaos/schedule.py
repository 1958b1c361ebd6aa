"""Seeded chaos schedules.

``build_schedule(seed, rounds)`` is a pure function: the same seed and
round count produce the identical event list on every machine and every
run — a run's whole fault sequence (which seam, which kind, which
stall length, in which order) derives from one integer.  The first
``len(FAULT_CLASSES)`` rounds are a deterministic shuffle covering every
fault class once (so per-class time-to-ready is always measurable);
remaining rounds draw uniformly.
"""

from __future__ import annotations

import random
from dataclasses import dataclass, field
from typing import List

__all__ = ["FAULT_CLASSES", "FaultEvent", "build_schedule"]

# fault class -> (fault point, action kind).  The catalog of seams wired
# through ``chaos.fault`` — see README "Robustness & chaos".
FAULT_CLASSES = {
    "log_enospc": ("delta_log.append", "enospc"),
    "log_torn": ("delta_log.append", "torn"),
    "repl_drop": ("repl.server.send", "drop"),
    "repl_garbage": ("repl.server.send", "garbage"),
    "repl_stall": ("repl.server.send", "stall"),
    # stall with the hold sampled per fire from the injector's seeded
    # lognormal (no fixed stall_s in the event data) — the heavy-tailed
    # degradation the photonwatch SLO burn episodes alarm on
    "repl_stall_dist": ("repl.server.send", "stall_dist"),
    "client_drop": ("repl.client.read", "drop"),
    "front_drop": ("front.conn", "drop"),
    "snapshot_disconnect": ("repl.server.snapshot", "disconnect"),
    "swap_crash": ("swap.activate", "crash"),
}


@dataclass(frozen=True)
class FaultEvent:
    """One chaos round: arm ``point`` with ``kind``, drive traffic,
    disarm, wait for the topology to heal."""

    round: int
    fault_class: str
    point: str
    kind: str
    data: dict = field(default_factory=dict)


def build_schedule(seed: int, rounds: int) -> List[FaultEvent]:
    """Deterministic event list: coverage pass over every fault class
    (shuffled by ``seed``), then seeded uniform draws."""
    rng = random.Random(seed)
    classes = sorted(FAULT_CLASSES)
    order = list(classes)
    rng.shuffle(order)
    picks = [order[i] if i < len(order) else rng.choice(classes)
             for i in range(rounds)]
    events = []
    for i, cls in enumerate(picks):
        point, kind = FAULT_CLASSES[cls]
        data = {}
        if kind == "stall":
            data["stall_s"] = round(rng.uniform(0.02, 0.10), 4)
        events.append(FaultEvent(round=i, fault_class=cls, point=point,
                                 kind=kind, data=data))
    return events
