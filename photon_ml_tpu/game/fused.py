"""Fully-jitted GLMix coordinate-descent sweeps.

The host-orchestrated ``CoordinateDescent`` (descent.py) mirrors the
reference's driver loop (CoordinateDescent.scala:119-346): one device
dispatch per solve/score plus host-side residual bookkeeping between
coordinates.  That loop is the right place for validation, checkpointing and
locked coordinates — but for raw training throughput the whole sweep can be
ONE XLA program: ``lax.scan`` over outer iterations whose body chains every
coordinate's traceable step (``Coordinate.trace_update``), residual fold, and
re-scoring.  No host round-trips, no per-phase dispatch latency, and XLA
overlaps/fuses across phases (e.g. the residual subtraction folds into the
next solver's first objective pass).

This is the TPU-native answer to the reference's persist/broadcast
choreography between coordinate updates (CoordinateDescent.scala:208-232):
instead of caching RDD scores between Spark jobs, the scores never leave HBM.

Every coordinate flavor is fused-eligible.  Per-update down-sampling runs
inside the program (a per-(iteration, coordinate) fold of the sweep's PRNG
key); coefficient variances are computed in the scan body on the final
iteration only, at the exact offsets/weights/reg of that coordinate's last
update (what the host loop publishes); projected random effects solve in
their compact per-bucket spaces and back-project inside ``trace_publish``.
Only per-fit HOST work (validation suites, checkpoint hooks, locked
coordinates, resume) forces the host-paced CoordinateDescent.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_ml_tpu.game.coordinate import Coordinate
from photon_ml_tpu.models.game import GameModel
from photon_ml_tpu.obs import get_registry
from photon_ml_tpu.obs.trace import (device_scope, get_tracer,
                                     hlo_collectives, hlo_op_table,
                                     metadata_keyed_compile_cache)
from photon_ml_tpu.obs.trace import enabled as obs_enabled
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.parallel.mesh import samples_on_device
from photon_ml_tpu.types import VarianceComputationType

Array = jax.Array


@jax.jit
def _take_iteration(pubs, t):
    """Iteration ``t`` (traced: one program whatever it is) of every
    coordinate's stacked published coefficients."""
    return tuple(p[t] for p in pubs)


# span attribute -> field of an executable's ``memory_analysis()``
_MEMORY_FIELDS = {"argument_bytes": "argument_size_in_bytes",
                  "output_bytes": "output_size_in_bytes",
                  "alias_bytes": "alias_size_in_bytes",
                  "temp_bytes": "temp_size_in_bytes",
                  "code_bytes": "generated_code_size_in_bytes"}


def _memory_account(compiled) -> Dict[str, int]:
    """What ``compiled`` says it needs on ONE device, by ``_MEMORY_FIELDS``'
    names; ``{}`` on a backend that keeps no such count (absent, not 0)."""
    try:
        stats = compiled.memory_analysis()
    except (NotImplementedError, RuntimeError):  # a backend without one
        return {}
    return {name: int(getattr(stats, field))
            for name, field in _MEMORY_FIELDS.items()
            if getattr(stats, field, None) is not None}


class FusedSweep:
    """jit(scan)-compiled block coordinate descent over GAME coordinates.

    Semantics match ``CoordinateDescent.run`` with no validation suite: cold
    start (or ``initial`` warm start), residual offsets, warm start across
    outer iterations, final full model returned.  Compiles ONE sweep body
    regardless of ``num_iterations``.
    """

    def __init__(self, coordinates: Dict[str, Coordinate],
                 order: Optional[Sequence[str]] = None,
                 num_iterations: int = 1):
        if not coordinates:
            raise ValueError("FusedSweep needs at least one coordinate")
        self.coordinates = coordinates
        self.order = list(order) if order is not None else list(coordinates)
        # positional carries double-count a repeated coordinate's score, so a
        # duplicate id must be rejected (the host descent tolerates repeats)
        if len(self.order) != len(coordinates) or set(self.order) != set(coordinates):
            raise ValueError(f"order {self.order} != ids {set(coordinates)}")
        self.num_iterations = num_iterations

        first = coordinates[self.order[0]]
        self._n = first.num_samples
        self._dtype = first.dtype
        # under a mesh the program's [n] vectors (base offsets, scores) have
        # their sample axis over every device, padded to the devices'
        # multiple (Coordinate.carry_samples); every host-facing result is
        # cut back to n
        self._mesh = getattr(first, "mesh", None)
        order, coords = self.order, self.coordinates

        needs_var = [coords[cid].config.variance != VarianceComputationType.NONE
                     for cid in self.order]
        needs_rand = [getattr(coords[cid].config, "down_sampling_rate", 1.0) < 1.0
                      for cid in self.order]
        self._needs_var = needs_var
        self._needs_rand = needs_rand
        self._snap_program = None  # built lazily by run_snapshots
        self._grid_program = None  # built lazily by run_grid
        self._grid_snap_program = None  # built lazily by run_grid_snapshots
        self._val_programs = {}    # built lazily by run_validated
        self._val_tables = set()   # their op-to-layer tables (traced runs)
        self._table_recorded = False  # the main program's op-to-layer table
        self._collectives = {}  # its collective instructions (traced runs)
        self.solve_iterations = None  # the last run_device's (see there)

        def program(states0, scores0, vars0, regs, base_key, base, datas):
            # regs: per-coordinate Regularization pytree, TRACED — a
            # reg-weight grid re-enters this one compiled program.
            # base_key: sweep PRNG key, folded per (iteration, coordinate)
            # for stochastic per-update work (down-sampling) — a new draw
            # each outer iteration, like the reference's seed-per-update
            # (DistributedOptimizationProblem.runWithSampling).  Folds are
            # emitted only for coordinates that down-sample, so the common
            # no-sampling program carries no threefry code at all.
            # base/datas: base offsets + per-coordinate design-matrix pytrees
            # as ARGUMENTS — closed-over device arrays would lower to baked
            # XLA constants, with compile time linear in constant bytes.
            def body(carry, it):
                states, scores, vars_, solved = carry
                vars_ = list(vars_)
                it_key = (jax.random.fold_in(base_key, it)
                          if any(needs_rand) else None)
                iterations = []
                states, scores, partials, keys = self._sweep_iteration(
                    states, scores, regs, it_key, base, datas,
                    iterations_out=iterations)
                for i, cid in enumerate(order):
                    if needs_var[i]:
                        # Only the LAST update's variances survive into the
                        # published model (host-path semantics), so skip the
                        # curvature work on every earlier iteration — FULL
                        # variance is a d×d Hessian + Cholesky per lane.
                        with device_scope("variances"):
                            vars_[i] = lax.cond(
                                it == self.num_iterations - 1,
                                lambda s, o, r, k: coords[cid].trace_variances(
                                    s, o, reg=r, key=k, data=datas[i]),
                                lambda s, o, r, k: vars_[i],
                                states[i], base + partials[i], regs[i],
                                keys[i])
                with device_scope("solve_iterations"):
                    solved = tuple(a.at[it].set(new)
                                   for a, new in zip(solved, iterations))
                return (tuple(states), tuple(scores), tuple(vars_),
                        solved), None

            # solved[i]: int32 [num_iterations, solves, 4], the sum and the
            # maximum of solver iterations over each solve's problems, then
            # of their line-search trials (Coordinate.trace_update's
            # ``iterations_out``)
            solved0 = tuple(
                jnp.zeros((self.num_iterations, coords[cid].num_solves, 4),
                          jnp.int32) for cid in order)
            carry, _ = lax.scan(body, (states0, scores0, vars0, solved0),
                                jnp.arange(self.num_iterations))
            states, scores, vars_, solved = carry
            published = tuple(coords[cid].trace_publish(states[i],
                                                        data=datas[i])
                              for i, cid in enumerate(order))
            return published, scores, vars_, solved

        self._program_fn = program  # unjitted: the grid path vmaps it
        self._program = jax.jit(program)
        self._base = self._samples(first._base_offset_host())
        self._datas = tuple(coords[cid].sweep_data() for cid in self.order)
        # Cold-start carry built eagerly: surfaces a coordinate without the
        # traceable-step interface at construction time (base-class
        # init_sweep_state raises) and is reused by run().
        self._cold = self._init_carry(None)
        self._vars0 = tuple(coordinates[cid].init_sweep_variances()
                            for cid in self.order)

    def _sweep_iteration(self, states, scores, regs, it_key, base, datas,
                         on_update=None, iterations_out=None):
        """Traceable: ONE outer iteration's coordinate loop — the single
        source of the descent math (residual fold + per-coordinate update,
        CoordinateDescent.scala:197-204) shared by the main program, the
        snapshot program and the validated program.  Returns (states',
        scores', partials, keys): partials[i] is the residual offset
        coordinate i was solved against and keys[i] the PRNG key its update
        used — variance computation must see the SAME offsets and
        down-sampling mask as the published coefficients, so it re-uses both
        rather than re-deriving them.  ``on_update(i, cid, state_i)``:
        traced hook after each coordinate's update (the validated program's
        per-update held-out bookkeeping).  ``iterations_out``: a list that
        gets one array of solver-iteration and line-search-trial counts per
        coordinate (``Coordinate.trace_update``)."""
        order, coords = self.order, self.coordinates
        needs_rand = self._needs_rand
        states, scores = list(states), list(scores)
        partials, keys = [], []
        # device_scope: every op below carries its layer in the executable's
        # metadata (obs/trace.py; the vocabulary is PERF.md section 3's)
        with device_scope("residual"):
            total = scores[0]
            # photonlint: disable=tracer-safety -- scores is a Python list
            # with one entry per coordinate (static length at trace time);
            # the loop unrolls over coordinates, not over a traced array's
            # elements
            for s in scores[1:]:
                total = total + s
        for i, cid in enumerate(order):
            with device_scope("update", cid):
                with device_scope("residual"):
                    # residual trick (CoordinateDescent.scala:197-204)
                    partial = total - scores[i]
                    offsets = base + partial
                key = (jax.random.fold_in(it_key, i) if needs_rand[i]
                       else None)
                states[i], scores[i] = coords[cid].trace_update(
                    states[i], offsets, reg=regs[i], key=key, data=datas[i],
                    iterations_out=iterations_out)
                partials.append(partial)
                keys.append(key)
                with device_scope("residual"):
                    total = partial + scores[i]
                if on_update is not None:
                    on_update(i, cid, states[i])
        return states, scores, partials, keys

    def _samples(self, v: np.ndarray) -> Array:
        """A host ``[n]`` vector as the program carries it."""
        return samples_on_device(v, self._mesh, self._dtype)

    def _init_carry(self, initial: Optional[GameModel]):
        states, scores = [], []
        for cid in self.order:
            coord = self.coordinates[cid]
            init = initial[cid] if initial is not None and cid in initial else None
            states.append(coord.init_sweep_state(init))
            if init is None:
                scores.append(jnp.zeros(self._n, self._dtype)
                              if self._mesh is None
                              else self._samples(np.zeros(self._n)))
                continue
            s = np.asarray(coord.score(init), self._dtype)
            c = coord.carry_through_scores(init)
            if c is not None:
                # the carried (never-retrained) contribution rides the BASE
                # offsets for the whole program (_base_with_carry_through);
                # keeping it out of the per-coordinate carry score prevents
                # double-counting it in the first update's residual
                s = s - np.asarray(c, self._dtype)
            scores.append(self._samples(s))
        return tuple(states), tuple(scores)

    def init_carry(self, initial: Optional[GameModel]):
        """Public warm-start carry builder: callers re-running one sweep many
        times from the SAME initial model (tuning) compute this once and pass
        it via ``run(carry0=...)`` instead of re-scoring the initial model
        per call."""
        return self._cold if initial is None else self._init_carry(initial)

    def run_device(self, initial: Optional[GameModel] = None,
                   regs: Optional[Sequence] = None, seed: int = 0,
                   carry0=None):
        """One fused descent, DEVICE outputs only: returns
        ``(published, scores, vars_, carried)`` where the first three are
        the program's output pytrees of device arrays — nothing is pulled
        to host.  For benchmarking (time the sweep, not the [n]-vector
        downloads — over slow transports those dominate) and for callers
        that pipeline further device work; ``run()`` wraps this with the
        host export.  The program's fourth output, the solver iterations
        and line-search trials of every solve of every update, stays on the
        device as ``self.solve_iterations`` (one int32 [num_iterations,
        solves, 4] a coordinate: sum and maximum of the iterations over the
        solve's problems, then of the trials); a traced run fetches it into
        the span ``descent.solve_iterations``, which waits for the program
        and also says how each coordinate's line search evaluates a trial
        (``Coordinate.line_search``)."""
        if obs_enabled() and not self._table_recorded:
            self._table_recorded = True
            args, _ = self._program_args(initial, regs, seed, carry0)
            self._collectives = hlo_collectives(self._record_device_table(
                "jit_program", self._program, args))
        # no fence: this is the ENQUEUE (argument preparation + dispatch),
        # what the device waits for between back-to-back fits
        with obs_span("descent.dispatch"):
            args, carried = self._program_args(initial, regs, seed, carry0)
            published, scores, vars_, self.solve_iterations = self._program(
                *args)
        if obs_enabled():
            self._record_exchange()
            with obs_span("descent.solve_iterations") as sp:
                fetched = jax.device_get(self.solve_iterations)
                sp.set(coordinates=list(self.order),
                       lane_iterations=[a[..., 0].tolist() for a in fetched],
                       trips=[a[..., 1].tolist() for a in fetched],
                       lane_trials=[a[..., 2].tolist() for a in fetched],
                       trial_trips=[a[..., 3].tolist() for a in fetched],
                       line_search=[self.coordinates[cid].line_search
                                    for cid in self.order])
        return published, scores, vars_, carried

    def _program_args(self, initial, regs, seed, carry0):
        """(the main program's positional arguments, carried scores)."""
        carry = carry0 if carry0 is not None else self.init_carry(initial)
        if regs is None:
            regs = tuple(self.coordinates[cid].config.reg for cid in self.order)
        base, carried = self._base_with_carry_through(initial)
        return (*carry, self._vars0, tuple(regs), jax.random.PRNGKey(seed),
                base, self._datas), carried

    def _record_exchange(self) -> None:
        """Span ``descent.exchange`` (traced fits under a mesh): per
        coordinate and exchange kind, the bytes a chip sends in ONE FIT,
        from the shapes and shardings of what crosses chips
        (``Coordinate.exchange_bytes``, an update's, times the outer
        iterations; ``psum`` stays per objective evaluation: how many a
        fit makes is the solver's), and ``collectives``: the main program's
        collective instructions by name (``hlo_collectives``: what a device
        trace has to be read by)."""
        if self._mesh is None:
            return
        with obs_span("descent.exchange") as sp:
            sent = [self.coordinates[cid].exchange_bytes()
                    for cid in self.order]
            sp.set(coordinates=list(self.order), devices=self._mesh.size,
                   collectives=dict(self._collectives),
                   bytes_sent=[{k: b * (1 if k == "psum"
                                        else self.num_iterations)
                                for k, b in e.items()} for e in sent])

    def _record_device_table(self, name: str, program, args) -> str:
        """Once per program of a sweep object, traced runs only: which layer
        each instruction of ``program``'s executable belongs to, read off
        that executable's own text and kept with the tracer under ``name``
        (``hlo_op_table``; ``jit_program`` the main program's,
        ``jit_validated`` the validated one's).  Lowers with the call's own
        arguments, so the dispatch that follows finds this lowering and
        this executable in jit's own caches: ONE executable serves the
        table, the run and an operator's profile, and tracing adds no
        second compile or load.  Its persistent-cache key holds the
        metadata (the scopes are this tree's, whoever filled the cache):
        the first traced run in a cache compiles the program once more,
        later ones load it.  The span carries the executable's own account:
        ``instructions`` (the table's length), ``hlo_bytes`` (its text's)
        and, where the backend gives a ``memory_analysis()``, a device's
        ``argument_bytes``, ``output_bytes``, ``alias_bytes``,
        ``temp_bytes`` and ``code_bytes``.  Returns the executable's
        text."""
        with obs_span("descent.device_table", program=name) as sp:
            with metadata_keyed_compile_cache():
                compiled = program.lower(*args).compile()
            text = compiled.as_text()
            table = hlo_op_table(text)
            get_tracer().record_device_table(name, table)
            sp.set(instructions=len(table), hlo_bytes=len(text),
                   **_memory_account(compiled))
        return text

    def run(self, initial: Optional[GameModel] = None,
            regs: Optional[Sequence] = None, seed: int = 0,
            carry0=None) -> Tuple[GameModel, Dict[str, np.ndarray]]:
        """One fused descent; returns (model, per-coordinate final scores).

        ``regs``: per-coordinate (order-aligned) Regularization overrides —
        lets one compiled sweep serve a whole reg-weight grid (the caller
        typically reads them off rebind-updated configs).  ``seed``: PRNG
        seed for in-program stochastic work (down-sampling); a traced input,
        so varying it reuses the compiled program.  ``carry0``: precomputed
        ``init_carry`` result (overrides ``initial``)."""
        # the whole sweep is ONE device program — per-coordinate host spans
        # can't exist here; device_sync brackets actual execution, so the
        # fused span is comparable with the host loop's descent.update sum
        with obs_span("descent.fused_sweep", device_sync=True,
                      coordinates=len(self.order),
                      iterations=self.num_iterations):
            published, scores, vars_, carried = self.run_device(
                initial, regs, seed, carry0)
        models = {cid: self.coordinates[cid].export_model(np.asarray(published[i]))
                  for i, cid in enumerate(self.order)}
        final_scores = {cid: np.asarray(scores[i])[: self._n]
                        for i, cid in enumerate(self.order)}
        for cid, c in carried.items():
            # published scores include the carried contribution, exactly as
            # the host loop's re-scoring of the merged model does
            final_scores[cid] = final_scores[cid] + c
        models = self._attach_variances(models, vars_)
        models = self._merge_carry_through(models, initial)
        return GameModel(models=models), final_scores

    def _base_with_carry_through(self, initial: Optional[GameModel]):
        """(base offsets + carried-entity scores, per-coordinate carried
        scores).  Carried entities never retrain, so their contribution is a
        CONSTANT the program must see in its offsets — otherwise every
        residual after a coordinate's first in-program update would drop it,
        diverging from the host loop (which re-scores the merged model each
        update)."""
        carried = {}
        base = self._base
        if initial is not None:
            for cid in self.order:
                c = self.coordinates[cid].carry_through_scores(
                    initial[cid] if cid in initial else None)
                if c is not None:
                    carried[cid] = c
                    base = base + self._samples(c)
        return base, carried

    def _merge_carry_through(self, models, initial: Optional[GameModel]):
        """Warm-start state the program could not retrain (prior-model
        entities with no active data) passes through on host — the same
        leftOuterJoin semantics the host path applies
        (Coordinate.merge_carry_through)."""
        if initial is None:
            return models
        return {cid: self.coordinates[cid].merge_carry_through(
                    m, initial[cid] if cid in initial else None)
                for cid, m in models.items()}

    def run_snapshots(self, initial: Optional[GameModel] = None,
                      regs: Optional[Sequence] = None, seed: int = 0,
                      carry0=None) -> Sequence[GameModel]:
        """One fused descent, returning the FULL model after EVERY outer
        iteration (still one compiled program — the scan emits each
        iteration's published coefficients as its per-step output).

        This is what host-paced best-model retention needs from a fused
        sweep: the host loop compares full models at sweep boundaries only
        (descent.py, reference CoordinateDescent.scala:163-167), so a caller
        holding these snapshots can evaluate each on validation data and keep
        the best — without per-update host round-trips.  Used by the tuning
        fast path (tune/game_tuning.py) for multi-iteration configs.

        Variance computation is not supported here (the host loop publishes
        each update's own variances; per-snapshot variances would multiply
        the curvature work T-fold) — callers fall back to the host descent.
        """
        if any(self._needs_var):
            raise NotImplementedError(
                "run_snapshots does not compute coefficient variances; use "
                "run() (final model only) or the host CoordinateDescent")
        if self._snap_program is None:
            self._snap_program = jax.jit(self._snap_fn())
        carry = carry0 if carry0 is not None else self.init_carry(initial)
        if regs is None:
            regs = tuple(self.coordinates[cid].config.reg for cid in self.order)
        base, _carried = self._base_with_carry_through(initial)
        pubs, _scores = self._snap_program(
            *carry, tuple(regs), jax.random.PRNGKey(seed),
            base, self._datas)
        pubs = [np.asarray(p) for p in pubs]
        return [
            GameModel(models=self._merge_carry_through(
                {cid: self.coordinates[cid].export_model(pubs[i][t])
                 for i, cid in enumerate(self.order)}, initial))
            for t in range(self.num_iterations)
        ]

    def _snap_fn(self):
        """The snapshot program (shared by run_snapshots and the vmapped
        grid twin): same _sweep_iteration core as the main program (no
        variances), but each iteration ALSO publishes — scan stacks the
        published coefficients along a leading T axis."""
        order, coords = self.order, self.coordinates
        needs_rand = self._needs_rand

        def program(states0, scores0, regs, base_key, base, datas):
            def body(carry, it):
                states, scores = carry
                it_key = (jax.random.fold_in(base_key, it)
                          if any(needs_rand) else None)
                states, scores, _, _ = self._sweep_iteration(
                    states, scores, regs, it_key, base, datas)
                published = tuple(
                    coords[cid].trace_publish(states[i], data=datas[i])
                    for i, cid in enumerate(order))
                return (tuple(states), tuple(scores)), published

            (_, scores), pubs = lax.scan(
                body, (states0, scores0), jnp.arange(self.num_iterations))
            return pubs, scores

        return program

    # --- fused validated sweeps -----------------------------------------

    def validation_plan(self, data, suite) -> "ValidationPlan":
        """Build (once per held-out set) the device-resident inputs
        ``run_validated`` scores against — per-coordinate designs/slots via
        each coordinate's ``external_data``.  Raises NotImplementedError
        for a coordinate without the external-scoring interface (the
        estimator then falls back to the host-paced CoordinateDescent)."""
        return ValidationPlan(self, data, suite)

    def _validated_fn(self, loss, suite):
        """The validated program: the same ``_sweep_iteration`` core as the
        main program, with per-update held-out bookkeeping fused in —
        after every coordinate update the scanned body re-scores THAT
        coordinate's held-out margins from its published coefficients
        (scope ``photon.validate.score.<cid>``), folds them into the running
        held-out total with the same residual-style replace the training
        scores use, and records the weighted held-out loss
        (``photon.validate.loss``; the in-program twin of the host loop's
        per-update ``descent.validate`` evaluation).  At each sweep boundary
        it evaluates the whole metric suite on the held-out totals
        (``EvaluationSuite.trace_evaluate``, scopes
        ``photon.evaluate.<metric>``) — a validated multi-iteration fit is
        ONE XLA program whose host-bound outputs are a ``[T, evaluators]``
        and a ``[T, C]`` matrix; the held-out totals and every iteration's
        published coefficients stay on the device."""
        order, coords = self.order, self.coordinates
        needs_rand = self._needs_rand

        def program(states0, scores0, vscores0, regs, base_key, base, datas,
                    vdatas, val_base, suite_inputs):
            val_y, val_wt = suite_inputs["labels"], suite_inputs["weights"]
            wt_sum = jnp.maximum(val_wt.sum(), jnp.asarray(1e-30, self._dtype))

            def body(carry, it):
                states, scores, vscores = carry
                vscores = list(vscores)
                it_key = (jax.random.fold_in(base_key, it)
                          if any(needs_rand) else None)
                published = [None] * len(order)
                losses = []
                vtotal = vscores[0]
                # photonlint: disable=tracer-safety -- static per-coordinate
                # list, unrolled at trace time like _sweep_iteration's
                for s in vscores[1:]:
                    vtotal = vtotal + s

                def on_update(i, cid, state_i):
                    nonlocal vtotal
                    w_pub = coords[cid].trace_publish(state_i, data=datas[i])
                    with device_scope("validate.score", cid):
                        vm = coords[cid].trace_score_external(
                            w_pub, vdatas[i]).astype(self._dtype)
                        vtotal = vtotal - vscores[i] + vm
                    vscores[i] = vm
                    published[i] = w_pub
                    with device_scope("validate.loss"):
                        z = vtotal + val_base
                        losses.append((val_wt * loss.loss(z, val_y)).sum()
                                      / wt_sum)

                states, scores, _, _ = self._sweep_iteration(
                    states, scores, regs, it_key, base, datas,
                    on_update=on_update)
                metrics = suite.trace_evaluate(vtotal + val_base,
                                               suite_inputs)
                return ((tuple(states), tuple(scores), tuple(vscores)),
                        (tuple(published), vtotal, jnp.stack(losses),
                         metrics))

            carry, (pubs, vtotals, losses, metrics) = lax.scan(
                body, (states0, scores0, vscores0),
                jnp.arange(self.num_iterations))
            return pubs, vtotals, losses, metrics

        return program

    def _validated_program(self, plan: "ValidationPlan"):
        """(key, the jitted validated program) for ``plan``'s loss and
        evaluators: static program structure, so every plan over this
        sweep that asks for the same metrics shares one program (the
        held-out arrays and group layouts are its arguments)."""
        key = (plan.loss, tuple(plan.suite.evaluators))
        if key not in self._val_programs:
            self._val_programs[key] = jax.jit(
                self._validated_fn(plan.loss, plan.suite))
        return key, self._val_programs[key]

    def _validated_args(self, plan: "ValidationPlan", initial=None,
                        regs=None, seed: int = 0, carry0=None) -> tuple:
        """The validated program's positional arguments."""
        carry = carry0 if carry0 is not None else self.init_carry(initial)
        if regs is None:
            regs = tuple(self.coordinates[cid].config.reg
                         for cid in self.order)
        base, _carried = self._base_with_carry_through(initial)
        vscores0, val_base = plan.initial_state(initial)
        return (*carry, vscores0, tuple(regs), jax.random.PRNGKey(seed),
                base, self._datas, plan.datas, val_base, plan.inputs)

    def run_validated(self, plan: "ValidationPlan",
                      initial: Optional[GameModel] = None,
                      regs: Optional[Sequence] = None, seed: int = 0,
                      carry0=None):
        """One fused descent WITH the validation suite: training updates,
        held-out scoring, per-update held-out losses and the suite's
        metrics at every sweep boundary all run inside one compiled
        program; the host reads the ``[T, evaluators]`` metrics, keeps the
        best full model — the exact best-model retention the host loop
        applies (full models at sweep boundaries only,
        CoordinateDescent.scala:163-167 / descent.py) — and pulls the
        retained iteration's coefficients alone.  The held-out totals of
        every iteration stay on the device as ``plan.totals`` [T, n_val]
        (offsets not included), until the next run over the plan.

        Returns ``(best_model, evals, best_eval, losses)``: the retained
        GameModel, one EvaluationResults per outer iteration (boundary
        evaluations, in order), the best's results, and the in-program
        per-(iteration, coordinate) held-out loss matrix [T, C].

        Eligibility mirrors run_snapshots: no coefficient variances (the
        host loop publishes each update's own variances; per-snapshot
        variances would multiply the curvature work T-fold) — callers with
        variance-computing coordinates fall back to the host descent.
        Checkpoint hooks / locked coordinates / resume are host-loop work by
        definition and never reach here (game/estimator.py gates)."""
        if any(self._needs_var):
            raise NotImplementedError(
                "run_validated does not compute coefficient variances; use "
                "the host CoordinateDescent for variance-computing validated "
                "fits")
        key, program = self._validated_program(plan)
        args = self._validated_args(plan, initial, regs, seed, carry0)
        if obs_enabled() and key not in self._val_tables:
            self._val_tables.add(key)
            self._record_device_table("jit_validated", program, args)
        suite = plan.suite
        with obs_span("descent.fused_validated", device_sync=True,
                      coordinates=len(self.order),
                      iterations=self.num_iterations):
            pubs, plan.totals, losses, metrics = program(*args)
            # the program's host-bound outputs: [T, C] and [T, evaluators]
            losses = np.asarray(losses)
            metrics = np.asarray(metrics)
        with obs_span("validate.evaluate", rows=plan.n,
                      evaluators=[ev.name for ev in suite.evaluators],
                      groups={tag: layout.num_groups for tag, layout
                              in plan.inputs["layouts"].items()}):
            evals, best_t, best_ev = [], 0, None
            for t in range(self.num_iterations):
                ev = suite.results(metrics[t])
                evals.append(ev)
                # strict-improvement retention in iteration order —
                # identical tie-breaking to the host loop's better_than
                # chain
                if suite.better_than(ev, best_ev):
                    best_ev, best_t = ev, t
        with obs_span("validate.export", iteration=best_t):
            # the retained iteration's coefficients alone cross to the host
            kept = jax.device_get(_take_iteration(pubs, np.int32(best_t)))
            models = {cid: self.coordinates[cid].export_model(
                          np.asarray(kept[i]))
                      for i, cid in enumerate(self.order)}
            model = GameModel(
                models=self._merge_carry_through(models, initial))
        get_registry().inc("validate.pull_bytes", losses.nbytes
                           + metrics.nbytes + sum(k.nbytes for k in kept))
        return model, evals, best_ev, losses

    # --- regularization-grid batching -----------------------------------
    # A λ grid's descents are INDEPENDENT programs over the SAME data, and
    # these solves are bandwidth-bound: vmapping the sweep over the reg
    # axis shares every design-matrix stream, so a B-point grid costs far
    # less than B sequential sweeps.  The reference trains its grid
    # sequentially (GameEstimator.fit over configurations;
    # GameEstimatorEvaluationFunction.apply per tuning iteration) — this is
    # the TPU-native replacement.  All grid lanes must share the L1 regime
    # (same static constraint as run()'s reg overrides, see sweep_key).

    def _stack_regs(self, regs_grid: Sequence[Sequence]) -> tuple:
        return jax.tree.map(
            lambda *leaves: jnp.stack(
                [jnp.asarray(v, self._dtype) for v in leaves]),
            *[tuple(regs) for regs in regs_grid])

    def run_grid(self, regs_grid: Sequence[Sequence],
                 initial: Optional[GameModel] = None, seed: int = 0,
                 carry0=None) -> list:
        """B fused descents over a regularization grid in ONE vmapped
        program; returns a list of B (model, scores-dict) pairs, each
        exactly what run() returns for that grid point."""
        if self._grid_program is None:
            self._grid_program = jax.jit(jax.vmap(
                self._program_fn,
                in_axes=(None, None, None, 0, None, None, None)))
        carry = carry0 if carry0 is not None else self.init_carry(initial)
        base, carried = self._base_with_carry_through(initial)
        published, scores, vars_, _iterations = self._grid_program(
            *carry, self._vars0, self._stack_regs(regs_grid),
            jax.random.PRNGKey(seed), base, self._datas)
        # one bulk device->host transfer per output array, host-indexed per
        # grid point (B*C per-slice transfers would multiply round-trip
        # latency on slow transports)
        published = [np.asarray(jax.device_get(p)) for p in published]
        scores = [np.asarray(s)[..., : self._n] for s in scores]
        vars_ = tuple(np.asarray(v) for v in vars_)
        out = []
        for b in range(len(regs_grid)):
            models = {cid: self.coordinates[cid].export_model(published[i][b])
                      for i, cid in enumerate(self.order)}
            final_scores = {cid: scores[i][b]
                            for i, cid in enumerate(self.order)}
            for cid, c in carried.items():
                final_scores[cid] = final_scores[cid] + c
            models = self._attach_variances(
                models, tuple(v[b] for v in vars_))
            models = self._merge_carry_through(models, initial)
            out.append((GameModel(models=models), final_scores))
        return out

    def run_grid_snapshots(self, regs_grid: Sequence[Sequence],
                           initial: Optional[GameModel] = None, seed: int = 0,
                           carry0=None) -> list:
        """Grid twin of run_snapshots: returns a list of B lists of
        per-iteration GameModels (one list per grid point)."""
        if any(self._needs_var):
            raise NotImplementedError(
                "run_grid_snapshots does not compute coefficient variances; "
                "use run_grid() or the host CoordinateDescent")
        if self._grid_snap_program is None:
            self._grid_snap_program = jax.jit(jax.vmap(
                self._snap_fn(), in_axes=(None, None, 0, None, None, None)))
        carry = carry0 if carry0 is not None else self.init_carry(initial)
        base, _carried = self._base_with_carry_through(initial)
        pubs, _scores = self._grid_snap_program(
            *carry, self._stack_regs(regs_grid), jax.random.PRNGKey(seed),
            base, self._datas)
        pubs = [np.asarray(p) for p in pubs]  # [coord][B, T, ...]
        return [
            [GameModel(models=self._merge_carry_through(
                {cid: self.coordinates[cid].export_model(pubs[i][b][t])
                 for i, cid in enumerate(self.order)}, initial))
             for t in range(self.num_iterations)]
            for b in range(len(regs_grid))
        ]

    def _attach_variances(self, models, vars_):
        """Attach the in-sweep-computed variances (the LAST update's, exactly
        as the host loop publishes) to the exported models."""
        import dataclasses

        from photon_ml_tpu.models.game import FixedEffectModel
        from photon_ml_tpu.models.glm import Coefficients

        for i, cid in enumerate(self.order):
            coord = self.coordinates[cid]
            if coord.config.variance == VarianceComputationType.NONE:
                continue
            v = coord.export_variances(vars_[i])
            m = models[cid]
            if isinstance(m, FixedEffectModel):
                models[cid] = dataclasses.replace(
                    m, coefficients=Coefficients(
                        means=m.coefficients.means, variances=v))
            else:  # random effect: stacked per-entity variances
                models[cid] = dataclasses.replace(m, variances=v)
        return models


class ValidationPlan:
    """Device-resident held-out inputs for ``FusedSweep.run_validated``.

    Built ONCE per (sweep, held-out set, suite): per-coordinate scoring
    pytrees (``Coordinate.external_data`` — designs + trained-slot maps,
    uploaded or taken where they lie, once), and the suite's device inputs
    (``EvaluationSuite.device_inputs``: labels, weights — which the
    in-program loss reads too — and one group layout per id tag a Multi-
    evaluator groups by).  The per-fit constants (warm-start held-out
    margins, carried-entity contributions) are computed by
    ``initial_state`` at run time — they depend on the initial model, not
    the plan; a cold start's are built once.  ``totals``: the last run's
    held-out totals [T, n_val] on the device (``run_validated``).
    """

    def __init__(self, sweep: FusedSweep, data, suite):
        from photon_ml_tpu.core.losses import loss_for_task

        self.sweep = sweep
        self.data = data
        self.suite = suite
        self.n = data.num_samples
        self.offset = np.asarray(data.offset)
        with obs_span("validate.plan", rows=self.n):
            # raises NotImplementedError for a coordinate without the
            # external-scoring interface — callers fall back to the host
            # loop
            self.datas = tuple(
                sweep.coordinates[cid].external_data(data)
                for cid in sweep.order)
            self.inputs = suite.device_inputs(data.y, data.weight,
                                              data.id_tags, sweep._dtype)
        first = sweep.coordinates[sweep.order[0]]
        self.loss = loss_for_task(first.task)
        self.totals = None
        self._cold = None

    def initial_state(self, initial):
        """(per-coordinate initial held-out margins, ``val_base`` = offsets
        + carried-entity contributions), device arrays — the held-out twin
        of ``FusedSweep._init_carry`` + ``_base_with_carry_through``:
        warm-start models contribute their held-out score from the start,
        carried (never-retrained) entities ride the base as a constant so
        every in-program replace matches the host loop's full-model
        re-scoring."""
        sweep = self.sweep
        dtype = sweep._dtype
        if initial is None:
            if self._cold is None:
                self._cold = (
                    tuple(jnp.zeros(self.n, dtype) for _ in sweep.order),
                    jnp.asarray(np.asarray(self.offset, dtype)))
            return self._cold
        val_base = np.asarray(self.offset, dtype).copy()
        vscores = []
        for i, cid in enumerate(sweep.order):
            coord = sweep.coordinates[cid]
            init = initial[cid] if cid in initial else None
            if init is None:
                vscores.append(jnp.zeros(self.n, dtype))
                continue
            s = np.asarray(init.score(self.data), dtype)
            c = coord.carry_through_scores_on(init, self.data)
            if c is not None:
                # carried contribution rides val_base for the whole program
                # (same no-double-count split as _init_carry's)
                s = s - np.asarray(c, dtype)
                val_base += np.asarray(c, dtype)
            vscores.append(jnp.asarray(s))
        return tuple(vscores), jnp.asarray(val_base)
