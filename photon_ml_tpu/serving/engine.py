"""AOT-compiled online scoring engine.

Photon ML reference counterpart: transformers/GameTransformer.scala — score
a prepared dataset with a GameModel by summing per-coordinate scores.  The
online twin differs in three accelerator-driven ways:

  1. **AOT compilation.**  Every (model-shape-signature, bucket-size) pair
     is lowered and compiled ONCE up front (``jax.jit(...).lower(...)
     .compile()``); requests only ever call finished executables, so the
     tail latency of a first-compile (tens of seconds on TPU) can never
     land on a user request.
  2. **Bucketed shapes.**  The batcher pads each micro-batch to a fixed
     ladder of bucket sizes, so the executable cache stays small and the
     second-and-later request at any bucket size triggers zero recompiles
     (``compile_count`` exposes this for tests/monitoring).
  3. **Composition parity.**  The kernel composes per-coordinate margins
     with the SAME ``game/scoring.additive_total`` and the same contraction
     primitives (``parallel/bucketing.score_samples``, ``x @ w``) the batch
     path uses, so serving scores are bitwise the ``GameTransformer`` batch
     scores — the property test in tests/test_serving.py holds this line.

Hot swap: ``activate`` flips the generation pointer atomically; requests
already scoring keep the store they snapshotted (serving/swap.py).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.chaos.injector import fault as _chaos_fault
from photon_ml_tpu.game.scoring import additive_total, output_scores
from photon_ml_tpu.obs import get_probe
from photon_ml_tpu.obs.trace import enabled as obs_enabled
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.parallel.bucketing import score_samples
from photon_ml_tpu.serving.batcher import (AsyncBatcher, BucketedBatcher,
                                           Request, densify_features)
from photon_ml_tpu.serving.coefficient_store import (CoefficientStore,
                                                     CompactRandomCoordinate,
                                                     FixedCoordinate)
from photon_ml_tpu.serving.metrics import ServingMetrics
from photon_ml_tpu.utils.logging import Timed

Array = jax.Array


def _cold_margin(x: Array, overflow: Array) -> Array:
    """Cold-entity contribution: the same per-row contraction
    ``score_samples`` applies to device-table rows, on host-gathered rows
    (zeros for hot/unknown samples -> adds exactly 0.0)."""
    return jnp.einsum("nd,nd->n", x, overflow)


class KernelCache:
    """Shared AOT-executable cache: ``(store.signature(), bucket)`` -> exe.

    One engine owns a private cache by default; a ``serving.fleet.ModelFleet``
    hands ONE cache to every per-model engine so same-signature models share
    compiled executables outright and distinct-shape models coexist side by
    side — the compiled-program family stays fixed as tenancy grows.

    Pruning is liveness-based rather than pairwise: each engine registers
    its ACTIVE store's signature under its own identity (``note_live``), and
    ``prune`` drops only keys no live store (plus explicitly kept retiring
    signatures) can ever reach again.  A single-engine cache degenerates to
    exactly the old keep-{old, new} behavior.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._executables: Dict[Tuple, object] = {}
        self.compile_count = 0  # compiles performed into THIS cache
        self._live: Dict[int, Tuple] = {}  # id(owner) -> active signature

    def __len__(self) -> int:
        with self._lock:
            return len(self._executables)

    def note_live(self, owner: object, signature: Tuple) -> None:
        """Record ``owner``'s (an engine's) active-store signature — the
        set of live signatures is what ``prune`` preserves."""
        with self._lock:
            self._live[id(owner)] = signature

    def drop_owner(self, owner: object) -> None:
        """Forget an engine that will never score again (fleet eviction)."""
        with self._lock:
            self._live.pop(id(owner), None)

    def get(self, key: Tuple):
        with self._lock:
            return self._executables.get(key)

    def put(self, key: Tuple, exe: object) -> None:
        with self._lock:
            self._executables[key] = exe
            self.compile_count += 1

    def prune(self, keep_extra: Sequence[Tuple] = ()) -> None:
        """Drop executables no live store can reach.  ``keep_extra`` holds
        retiring signatures in-flight requests may still be scoring on."""
        with self._lock:
            keep = set(self._live.values()) | set(keep_extra)
            self._executables = {k: v for k, v in self._executables.items()
                                 if k[0] in keep}

    def signatures(self) -> Tuple[Tuple, ...]:
        """Distinct signatures currently cached (tests/introspection)."""
        with self._lock:
            return tuple({k[0] for k in self._executables})


class ScoringEngine:
    """Low-latency scorer over a CoefficientStore (see module docstring)."""

    def __init__(self, store: CoefficientStore,
                 batcher: Optional[BucketedBatcher] = None,
                 metrics: Optional[ServingMetrics] = None,
                 kernels: Optional[KernelCache] = None):
        self._store = store
        self.batcher = batcher or BucketedBatcher()
        self.metrics = metrics or ServingMetrics()
        self._lock = threading.Lock()
        # private by default; a ModelFleet passes one shared cache to every
        # per-model engine so same-shape models never compile twice
        self.kernels = kernels or KernelCache()
        self.kernels.note_live(self, store.signature())
        self.compile_count = 0  # compiles THIS engine performed

    # -- generation management (hot swap) ----------------------------------
    @property
    def store(self) -> CoefficientStore:
        return self._store

    def activate(self, store: CoefficientStore) -> CoefficientStore:
        """Atomically flip the serving generation; returns the old store.
        In-flight requests snapshotted the old store and finish on it."""
        with obs_span("serve.activate", generation=store.generation):
            with self._lock:
                old, self._store = self._store, store
            # executables no LIVE store (any engine on this cache) can
            # reach again are dropped so repeated swaps stay bounded; the
            # retiring signature is kept for in-flight requests that
            # snapshotted the old store
            self.kernels.note_live(self, store.signature())
            self.kernels.prune(keep_extra=(old.signature(),))
            self.metrics.inc("activations")
        return old

    # -- compilation -------------------------------------------------------
    def warm(self, buckets: Optional[Sequence[int]] = None,
             store: Optional[CoefficientStore] = None) -> int:
        """Compile executables for ``buckets`` (default: the batcher's whole
        ladder) against ``store`` (default: active).  Returns how many were
        newly compiled.  Hot swap warms the NEW store here before flipping
        the pointer, so no request ever waits on a compile."""
        store = store or self._store
        buckets = tuple(buckets) if buckets is not None \
            else self.batcher.bucket_sizes
        before = self.compile_count
        with Timed(f"serving.warm gen{store.generation}",
                   sink=self.metrics.phase):
            for b in buckets:
                self._executable(store, b)
        return self.compile_count - before

    def _abstract_args(self, store: CoefficientStore, bucket: int):
        """ShapeDtypeStructs matching _concrete_args.  Compact coordinates
        ride the SAME (tables, slots, overflows) argument slots with
        (indices, values) PAIRS as the pytree leaves — one executable
        signature for every coordinate mix."""
        s = jax.ShapeDtypeStruct
        x_dt = np.dtype(store.config.x_dtype)
        xs = {shard: s((bucket, d), x_dt)
              for shard, d in store.shard_dims.items()}
        fixed_ws, tables, slots, overflows = [], [], [], []
        for cid in store.order:
            c = store.coordinates[cid]
            if isinstance(c, FixedCoordinate):
                fixed_ws.append(s(c.weights.shape, c.weights.dtype))
                continue
            # sharded stores pin the hot tables' mesh layout into the AOT
            # signature — lowering bakes the shard-local kernel in, and the
            # executable rejects a mislaid table instead of silently
            # gathering it
            sh = None if c.shard_spec is None else c.shard_spec.sharding
            if isinstance(c, CompactRandomCoordinate):
                hs = c.hot
                tables.append(
                    (s(hs.indices.shape, hs.indices.dtype, sharding=sh),
                     s(hs.values.shape, hs.values.dtype, sharding=sh)))
                slots.append(s((bucket,), np.dtype(np.int32)))
                overflows.append((s((bucket, c.k), np.dtype(np.int32)),
                                  s((bucket, c.k), hs.values.dtype)))
            else:
                tables.append(s(c.table.shape, c.table.dtype, sharding=sh))
                slots.append(s((bucket,), np.dtype(np.int32)))
                overflows.append(s((bucket, c.dim), c.table.dtype))
        return xs, fixed_ws, tables, slots, overflows

    def _build_fn(self, store: CoefficientStore, bucket: int):
        order = list(store.order)
        mesh = store.mesh

        def _kind(c):
            if isinstance(c, FixedCoordinate):
                return "fixed"
            return "compact" if isinstance(c, CompactRandomCoordinate) \
                else "dense"

        # (cid, kind, feature shard, per-shard hot rows | None if unsharded)
        kinds = []
        for cid in order:
            c = store.coordinates[cid]
            local_rows = None
            if getattr(c, "shard_spec", None) is not None:
                rows = (c.hot.indices.shape[0]
                        if isinstance(c, CompactRandomCoordinate)
                        else c.table.shape[0])
                local_rows = rows // c.shard_spec.n_shards
            kinds.append((cid, _kind(c), c.feature_shard, local_rows))

        if mesh is not None:
            # pod-slice kernels: each shard scores ONLY the slots whose
            # global device row lives in its table block, then the psum
            # folds the per-shard partial margins — the [bucket] score
            # vector is the only thing that crosses ICI; coefficient rows
            # never leave their shard (no all-gather, by construction)
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            from photon_ml_tpu.parallel.mesh import SHARD_AXIS

            def _localize(s, cap):
                # global row -> this shard's local row; -1 (scores 0.0 by
                # the kernels' masking contract) for rows owned elsewhere.
                # PLACEMENT-AGNOSTIC: the kernel only asks "is this global
                # row in my block", so traffic-aware routing and hot-row
                # replication (coefficient_store) change WHICH rows hold
                # an entity without touching this path — exactly one shard
                # owns any resolved row and the rest contribute 0.0 to the
                # psum, which is why scores stay bitwise identical under
                # any routing table
                sid = jax.lax.axis_index(SHARD_AXIS)
                loc = s - sid * cap
                mine = (s >= 0) & (loc >= 0) & (loc < cap)
                return jnp.where(mine, loc, -1)

            def _sharded_dense(cap):
                def local_fn(t, s, xx):
                    return jax.lax.psum(
                        score_samples(t, _localize(s, cap), xx), SHARD_AXIS)
                return shard_map(local_fn, mesh=mesh,
                                 in_specs=(P(SHARD_AXIS), P(), P()),
                                 out_specs=P())

            def _sharded_compact(cap):
                def local_fn(ti, tv, s, xx):
                    from photon_ml_tpu.models.game import score_compact_dense
                    return jax.lax.psum(
                        score_compact_dense(ti, tv, _localize(s, cap), xx),
                        SHARD_AXIS)
                return shard_map(
                    local_fn, mesh=mesh,
                    in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P()),
                    out_specs=P())

        def fn(xs, fixed_ws, tables, slots, overflows):
            from photon_ml_tpu.models.game import score_compact_dense

            margins = []
            fi = ri = 0
            for cid, kind, shard, local_rows in kinds:
                x = xs[shard]
                if kind == "fixed":
                    # == models/glm.Coefficients.score (x @ means)
                    margins.append(x @ fixed_ws[fi])
                    fi += 1
                elif kind == "compact":
                    # the SAME compact gather kernel batch scoring uses
                    # (models/game.score_compact_dense) for the hot rows,
                    # and the identical math on per-sample overflow rows
                    # (slots = iota: row i scores its own cold row; dim-
                    # padded hot/unknown rows contribute exactly 0.0)
                    t_idx, t_val = tables[ri]
                    o_idx, o_val = overflows[ri]
                    if local_rows is None:
                        m = score_compact_dense(t_idx, t_val, slots[ri], x)
                    else:
                        m = _sharded_compact(local_rows)(
                            t_idx, t_val, slots[ri], x)
                    # cold rows are host-gathered per sample and replicated;
                    # they stay outside the shard_map
                    cold = score_compact_dense(
                        o_idx, o_val, jnp.arange(bucket, dtype=jnp.int32), x)
                    margins.append(m + cold)
                    ri += 1
                else:
                    if local_rows is None:
                        m = score_samples(tables[ri], slots[ri], x)
                    else:
                        m = _sharded_dense(local_rows)(
                            tables[ri], slots[ri], x)
                    margins.append(m + _cold_margin(x, overflows[ri]))
                    ri += 1
            # the ONE additive composition (game/scoring.py) — shared with
            # GameModel.score so batch and serving totals cannot drift
            return additive_total(bucket, margins)

        return fn

    def _executable(self, store: CoefficientStore, bucket: int):
        key = (store.signature(), bucket)
        exe = self.kernels.get(key)
        if exe is not None:
            return exe
        fn = self._build_fn(store, bucket)
        # No buffer is donated: the per-request inputs ([bucket, d] features,
        # slots, overflow rows) share no shape with the [bucket] output, so
        # XLA has nothing to alias — on a v5e it answered a donation with
        # "Some donated buffers were not usable" for every executable.
        # probe accounting: every AOT compile is counted + timed under the
        # "serving.engine" site, so "did serving recompile after warm" is a
        # registry query that must agree with compile_count
        with get_probe().compile_span("serving.engine", bucket=bucket):
            lowered = jax.jit(fn).lower(*self._abstract_args(store, bucket))
            exe = lowered.compile()
        self.kernels.put(key, exe)
        with self._lock:
            self.compile_count += 1
        self.metrics.inc("compiles")
        return exe

    # -- scoring -----------------------------------------------------------
    def score_requests(self, requests: Sequence[Request],
                       predict_mean: bool = False,
                       store: Optional[CoefficientStore] = None) -> np.ndarray:
        """Score a request list; returns one score per request (raw margin +
        offset, or the task's inverse-link mean with ``predict_mean`` — the
        same output contract as cli/score.py).  ``store`` overrides the
        active generation for this call only — canary/shadow scoring
        (serving/fleet) scores a staged store without flipping the
        pointer; executables come from the same ``kernels`` cache."""
        if store is None:
            store = self._store  # snapshot: finish on one generation
        n = len(requests)
        self.metrics.inc("requests", n)
        if n == 0:
            return np.zeros(0)
        out: Optional[np.ndarray] = None
        for mb in self.batcher.plan(n):
            t0 = time.perf_counter()
            act = _chaos_fault("serve.execute")
            if act is not None:
                # chaos: hold the scoring path itself (stall/stall_dist) —
                # the latency-SLO degradation episodes alarm on; requests
                # still succeed, so availability objectives stay quiet.
                # Any other kind at this point is a seam misuse.
                if act.kind in ("stall", "stall_dist"):
                    time.sleep(float(act.data.get("stall_s", 0.05)))
                else:
                    raise act.to_error()
            chunk = requests[mb.start:mb.stop]
            attrs = {}
            if obs_enabled():
                # a chunk scores many requests: stamp every trace id it
                # carries, so the execute (and mesh psum) spans join each
                # request's cross-process timeline — same contract as the
                # batcher's serve.flush span
                tids = sorted({r.ctx[0] for r in chunk
                               if r.ctx is not None})
                if tids:
                    attrs["traces"] = tids
            with obs_span("serve.execute", bucket=mb.bucket,
                          rows=mb.real_rows, **attrs):
                scores = self._score_chunk(store, chunk, mb.bucket,
                                           trace_attrs=attrs)
            if out is None:
                out = np.empty(n, scores.dtype)
            out[mb.start:mb.stop] = scores[: mb.real_rows]
            self.metrics.observe_batch(mb.bucket, mb.real_rows,
                                       time.perf_counter() - t0)
        raw = out + np.asarray([r.offset for r in requests], out.dtype)
        return output_scores(raw, store.task, predict_mean=predict_mean)

    def _score_chunk(self, store: CoefficientStore,
                     chunk: Sequence[Request], bucket: int,
                     trace_attrs: Optional[dict] = None) -> np.ndarray:
        exe = self._executable(store, bucket)
        xs = densify_features(chunk, store.index_maps, bucket,
                              dtype=store.config.x_dtype)
        fixed_ws, tables, slots, overflows = [], [], [], []
        for cid in store.order:
            c = store.coordinates[cid]
            if isinstance(c, FixedCoordinate):
                fixed_ws.append(c.weights)
            else:
                names = [r.ids.get(c.random_effect_type) for r in chunk]
                # resolve pads rows beyond len(chunk) itself (slot -1, zero
                # overflow, not counted as misses) and returns the residency
                # snapshot the slots index — a concurrent rebalance can
                # never pair these slots with a different table
                tbl, sl, ov = store.resolve(cid, names, n_rows=bucket,
                                            metrics=self.metrics)
                if isinstance(c, CompactRandomCoordinate):
                    # compact snapshot -> the (indices, values) leaf pair
                    # _build_fn's compact branch consumes; overflow is
                    # already the ([n, k], [n, k]) pair
                    tables.append((tbl.indices, tbl.values))
                else:
                    tables.append(tbl)
                slots.append(sl)
                overflows.append(ov)
        if store.mesh is not None:
            # the executable's only cross-shard traffic is the margin psum;
            # trace_attrs carries the chunk's trace ids so the pod-slice
            # hop is attributable to the requests that crossed it
            with obs_span("serve.psum", shards=store.config.mesh_shards,
                          bucket=bucket, **(trace_attrs or {})):
                return np.asarray(exe(xs, fixed_ws, tables, slots, overflows))
        return np.asarray(exe(xs, fixed_ws, tables, slots, overflows))

    # -- async front -------------------------------------------------------
    def async_batcher(self, deadline_s: float = 500e-6,
                      predict_mean: bool = False,
                      flush_threshold: Optional[int] = None) -> AsyncBatcher:
        """An AsyncBatcher feeding this engine: submit requests one at a
        time, get score futures back; flushes on a full top bucket or the
        deadline, whichever first (see serving/batcher.AsyncBatcher)."""

        def score(reqs: Sequence[Request]) -> np.ndarray:
            return self.score_requests(reqs, predict_mean=predict_mean)

        return AsyncBatcher(
            score,
            flush_threshold=flush_threshold or self.batcher.max_batch,
            deadline_s=deadline_s, metrics=self.metrics)
