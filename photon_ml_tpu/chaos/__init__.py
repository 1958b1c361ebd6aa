"""photonchaos: deterministic fault injection, health/readiness, and
seeded chaos schedules.

Seam-side usage (one boolean check when disabled)::

    from photon_ml_tpu.chaos import fault

    act = fault("delta_log.append")
    if act is not None:
        raise act.to_error()

Test-side usage::

    from photon_ml_tpu.chaos import get_injector

    inj = get_injector()
    inj.arm("repl.server.send", kind="drop", nth=3)
    try:
        ...drive traffic, assert the topology heals...
    finally:
        inj.reset()
"""

from photon_ml_tpu.chaos.health import (HealthState, Watchdog, WorkerWatch,
                                        delta_log_check,
                                        follower_staleness_check)
from photon_ml_tpu.chaos.injector import (FaultAction, FaultInjector,
                                          InjectedCrash, InjectedFault,
                                          fault, get_injector, set_injector)
from photon_ml_tpu.chaos.schedule import (FAULT_CLASSES, FaultEvent,
                                          build_schedule)

__all__ = [
    "FAULT_CLASSES", "FaultAction", "FaultEvent", "FaultInjector",
    "HealthState", "InjectedCrash", "InjectedFault", "Watchdog",
    "WorkerWatch", "build_schedule", "delta_log_check", "fault",
    "follower_staleness_check", "get_injector", "set_injector",
]
