"""photonwatch: the fleet-global metrics plane.

Per-process observability (PR 5's registry/tracer, PR 15's pulse) answers
"what is THIS process doing"; photonwatch answers the questions that only
exist across the constellation:

* :mod:`federation` — each process exports its ``MetricsRegistry`` as a
  delta-compressed stream (``{"cmd": "watch"}`` on the serving socket, the
  ``/watchz`` HTTP route for pull), and a :class:`FleetView` merges N
  labeled snapshots into one global registry with staleness tracking.
* :mod:`slo` — declarative objectives evaluated as multi-window burn
  rates, publishing ``fleet_slo_burn_rate{slo=}`` gauges, latching alerts,
  and dumping the flight recorder on each burn edge.

Device time per site is not measured here: the descent program names its
layers on the device and says from its own executable which instruction
belongs to which (``obs/trace.py``: ``device_scope``, ``hlo_op_table``).
"""

from photon_ml_tpu.obs.watch.federation import (  # noqa: F401
    DeltaExporter,
    FleetView,
    apply_frame,
)
from photon_ml_tpu.obs.watch.slo import (  # noqa: F401
    SLO,
    SLOEngine,
    SLOEvalThread,
    load_slos,
)
