"""Online GLMix scoring — the serving half of the Photon ML design.

The reference trains GAME models in Spark and publishes them to PalDB
stores + broadcast coefficients for LinkedIn's online serving stack; this
package is that serving layer, TPU-native:

  - ``coefficient_store``: device-resident versioned coefficient tables
    (the PalDB analog) with a frequency-ranked hot set (EWMA hit counters
    + promotion/demotion rebalancing), an LRU host fallback for cold
    entities, and streaming per-entity delta updates;
  - ``batcher``: request micro-batching padded to a fixed bucket ladder so
    every shape hits an already-compiled executable, plus the async
    deadline accumulator (``AsyncBatcher``: submit one request, get a
    future; flushes on a full bucket or a ~500µs deadline);
  - ``engine``: AOT-lowered per-(signature, bucket) scoring kernels sharing
    the batch path's score composition (game/scoring.py);
  - ``swap``: atomic hot model reload (load -> warm -> flip) and the
    streaming-delta entry point (``(generation, delta_version)`` identity);
  - ``metrics``: the serving metrics facade (latency histograms, QPS,
    padding waste + per-bucket occupancy, hot-set hit rate, entity misses,
    flush mix, swap counters) over the unified ``obs.MetricsRegistry`` —
    JSON snapshot wire format preserved, Prometheus exposition added; the
    hot paths also emit ``obs`` tracer spans (submit → flush → resolve →
    execute) when tracing is on;
  - ``frontend``: the network edge — an asyncio TCP server multiplexing
    many clients into the AsyncBatcher with deadline-budget admission
    control (load shedding + hysteresis), per-client round-robin fairness,
    graceful drain on swap/SIGTERM, a ``/metrics`` scrape endpoint, and
    an open-loop Poisson load generator;
  - ``fleet``: multi-model serving — a keyed family of model handles
    sharing one AOT kernel cache and one device hot-row budget with
    per-tenant quotas, plus canary rollout (deterministic traffic split,
    auto-promote/auto-rollback) and shadow scoring.

``cli/serve.py`` wires these into a stdin/JSON-lines driver (or, with
``--listen``, the socket front end) and a programmatic ``build_server``
entry point.
"""

from photon_ml_tpu.serving.batcher import (AsyncBatcher, BucketedBatcher,  # noqa: F401
                                           Request, pow2_bucket_ladder,
                                           request_from_json)
from photon_ml_tpu.serving.coefficient_store import (CoefficientStore,  # noqa: F401
                                                     HotSetManager,
                                                     StoreConfig)
from photon_ml_tpu.serving.engine import KernelCache, ScoringEngine  # noqa: F401
from photon_ml_tpu.serving.fleet import (CanaryController,  # noqa: F401
                                         CanaryPolicy, ModelFleet,
                                         ModelHandle, ShadowScorer,
                                         TenantBudgetError,
                                         UnknownModelError)
from photon_ml_tpu.serving.metrics import ServingMetrics  # noqa: F401
from photon_ml_tpu.serving.swap import HotSwapper  # noqa: F401
