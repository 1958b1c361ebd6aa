"""photonfront — the network serving edge (ROADMAP item 2).

A stdlib-only asyncio TCP front end multiplexing many concurrent client
connections into the AOT serving stack, speaking the same newline-
delimited JSON wire protocol as the stdio ``cli/serve.py`` loop:

  - ``protocol``: bounded line framing (one oversized/malformed line gets
    an error reply; the connection survives);
  - ``admission``: deadline-budget load shedding with hysteresis, fed by
    ``AsyncBatcher.queue_wait_estimate``;
  - ``fairness``: per-client round-robin queue draining;
  - ``server``: the :class:`FrontendServer` tying those together with
    graceful drain on swap/delta/shutdown/SIGTERM;
  - ``metrics_http``: the ``GET /metrics`` Prometheus scrape endpoint;
  - ``loadgen``: the open-loop Poisson generator.

``cli/serve.py --listen host:port`` runs it; stdio stays the default.
"""

from photon_ml_tpu.serving.frontend.admission import (AdmissionConfig,  # noqa: F401
                                                      AdmissionController,
                                                      Verdict)
from photon_ml_tpu.serving.frontend.fairness import FairQueue  # noqa: F401
from photon_ml_tpu.serving.frontend.loadgen import (OpenLoopResult,  # noqa: F401
                                                    run_open_loop)
from photon_ml_tpu.serving.frontend.metrics_http import (  # noqa: F401
    MetricsEndpoint, ThreadedMetricsEndpoint)
from photon_ml_tpu.serving.frontend.protocol import (  # noqa: F401
    DEFAULT_MAX_LINE_BYTES, BoundedLineReader, LineTooLong,
    iter_bounded_lines)
from photon_ml_tpu.serving.frontend.server import (FrontendConfig,  # noqa: F401
                                                   FrontendServer,
                                                   ThreadedFrontend)
