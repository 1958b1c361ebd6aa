"""A GLMix sharded over a four-device mesh (ISSUE 30): ``glmix_ml25m``'s
ragged three-coordinate problem at its dry-run sizes (heavy-tailed counts
from ``recipes/ml20m_counts``, capped beside uncapped entities, passive
rows, 9,217 rows: a count that divides neither by 4 nor by a kernel block),
on four of conftest's eight virtual CPU devices.

(a) the fused sweep under ``make_mesh(devices[:4])`` against the plain
    reference (benchmarks/reference/glmix_descent.py) and against the
    one-device fit, over both solver sides and the four rescore layouts;
(b) structure: every array with a sample, chunk or lane axis sharded over
    all four devices, no collective with a design-sized operand, the fixed
    design where the caller put it, no collective and no exchange without a
    mesh that spans chips;
(c) tracing: the ``photon.exchange.<kind>`` scopes in the op table, the new
    attributes of ``coord.upload`` and ``coord.bucket``, the span
    ``descent.exchange``, the six readers of the cell's new metrics, and a
    traced ``--dry-run`` of the cell.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path[:0] = [BENCH, REPO]

import exchange_model  # noqa: E402
import run as harness  # noqa: E402

from photon_ml_tpu import obs  # noqa: E402
from photon_ml_tpu.core.regularization import Regularization  # noqa: E402
from photon_ml_tpu.game import (FixedEffectConfig, GameData,  # noqa: E402
                                RandomEffectConfig)
from photon_ml_tpu.game.coordinate import build_coordinate  # noqa: E402
from photon_ml_tpu.game.data import SparseShard  # noqa: E402
from photon_ml_tpu.game.fused import FusedSweep  # noqa: E402
from photon_ml_tpu.obs.trace import Tracer, set_tracer  # noqa: E402
from photon_ml_tpu.opt.types import SolverConfig  # noqa: E402
from photon_ml_tpu.parallel import bucketing  # noqa: E402
from photon_ml_tpu.parallel.mesh import (make_mesh, over_chips,  # noqa: E402
                                         padded_samples, samples_on_device,
                                         spans_chips)
from photon_ml_tpu.types import TaskType  # noqa: E402

CATALOG = harness.Catalog()
CELL = "glmix_ml25m.train_x4"
CHIPS = 4
TASK = TaskType.LOGISTIC_REGRESSION
# the two sides of the solver rule in game/coordinate.py (cap x d^2 against
# 2 x 1280 at the dry-run cap of 64): the vmapped L-BFGS, the SoA Newton
SIDES = {"lbfgs": 8, "soa_newton": 4}
LAYOUTS = ("row_major", "entity_major", "transposed", "sparse")
COLLECTIVE = re.compile(
    r"^\s+(?:ROOT\s+)?%?[\w.\-]+ = (.*?) (all-reduce|all-gather|all-to-all|"
    r"reduce-scatter|collective-permute)(?:-start)?\(", re.M)


def config():
    return harness.sized(CATALOG.json("configs", "glmix_ml25m"), True)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(devices=jax.devices()[:CHIPS])


def problem(d: int) -> dict:
    """The configuration's counts and id columns at its dry-run sizes,
    features of width ``d`` a random effect (8 the fixed one)."""
    recipe = CATALOG.module("recipes", "ml20m_counts")
    cfg = config()
    uids, iids = recipe.entity_columns(cfg, 11)
    n = len(uids)
    rng = np.random.default_rng(d)
    feats = {"g": rng.normal(size=(n, 8)), "u": rng.normal(size=(n, d)),
             "i": rng.normal(size=(n, d))}
    return dict(
        y=(rng.random(n) < 0.5).astype(np.float32),
        features={k: v.astype(np.float32) for k, v in feats.items()},
        id_tags={"userId": uids, "itemId": iids})


def coordinate_configs(cfg: dict) -> dict:
    # solved to the end, in float64 (build): the comparisons below are of
    # layouts, and a float32 solve's remainder (1e-3 of a coefficient,
    # ended by ulp luck: PERF.md section 6, PR 25) would hide what a
    # layout moved
    solver = SolverConfig(max_iters=60, tolerance=1e-12)
    reg = Regularization(l2=float(cfg["l2"]))
    out = {}
    for c in cfg["coordinates"]:
        out[c["id"]] = (
            FixedEffectConfig(feature_shard=c["feature_shard"], solver=solver,
                              reg=reg) if c["kind"] == "fixed" else
            RandomEffectConfig(random_effect_type=c["entity"],
                               feature_shard=c["feature_shard"], solver=solver,
                               reg=reg, active_cap=c["active_cap"]))
    return out


def build(data: dict, layout: str, mesh, dtype=np.float64) -> dict:
    """The three coordinates with the random effects' full-sample design
    in ``layout`` (the rule's lines moved for the build, as the tests of
    the layouts do: tests/test_driver_parity.py)."""
    features = dict(data["features"])
    with pytest.MonkeyPatch.context() as mp:
        if layout in ("entity_major", "transposed"):
            mp.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 1)
        if layout == "transposed":
            mp.setattr(bucketing, "EM_CHUNK_MIN", 2 * bucketing.EM_ROW)
        if layout == "sparse":
            for k in ("u", "i"):
                x = features[k]
                features[k] = SparseShard(
                    indices=np.tile(np.arange(x.shape[1], dtype=np.int32),
                                    (len(x), 1)), values=x, dim=x.shape[1])
        gd = GameData(y=data["y"], features=features,
                      id_tags=data["id_tags"])
        return {cid: build_coordinate(cid, gd, c, TASK, mesh, dtype=dtype)
                for cid, c in coordinate_configs(config()).items()}


def layout_of(coord) -> str:
    return ("sparse" if coord._sparse else "entity_major"
            if coord._em is not None else "transposed"
            if coord._x_full_is_t else "row_major")


@pytest.fixture(scope="module")
def baselines():
    """{side: (data, the one-device fit, the plain reference)}, each made
    once: neither depends on how a mesh lays the problem out."""
    made = {}

    def of(side):
        if side in made:
            return made[side]
        cfg, data = config(), problem(SIDES[side])
        coords = build(data, "row_major", None)
        assert coords["per-user"]._use_soa == (side == "soa_newton")
        model, scores = FusedSweep(
            coords, num_iterations=int(cfg["sweeps"])).run()
        active = {}
        for c in cfg["coordinates"][1:]:
            b = coords[c["id"]].buckets
            active[c["id"]] = {
                e: (rows := b.buckets[bi].rows[lane])[rows >= 0]
                for e, (bi, lane) in b.lane_of.items()}
        descent = CATALOG.module("reference", "glmix_descent")
        # the reference states float32 and is not written for conftest's x64
        jax.config.update("jax_enable_x64", False)
        try:
            ref = descent.descend(data["y"], data["features"],
                                  data["id_tags"], cfg["coordinates"],
                                  int(cfg["sweeps"]), float(cfg["l2"]), active)
        finally:
            jax.config.update("jax_enable_x64", True)
        made[side] = (data, (model, scores), ref)
        return made[side]

    return of


def relative(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def stacked(ws: dict, model) -> np.ndarray:
    out = np.zeros_like(np.asarray(model.w_stack, np.float32))
    for e, w in ws.items():
        out[model.slot_of[e]] = w
    return out


# -- (a) the answers do not depend on the layout ------------------------------

# Against the plain reference: float64 solves run to the end against a
# converged float32 Newton solve, so what is left is the REFERENCE's
# float32 (1.7e-4 to 3.0e-4 over the eight cases).  tests/
# test_ragged_glmix.py has 1.6e-3 for a float32 program against it, and
# reads 2.6e-3 and more with the features rounded to bfloat16.
REFERENCE_TOL = 6e-4
# Against the one-device fit: the same solves of the same problem.  Lanes
# are independent, the table and the vectors are copied and not summed, so
# only the fixed effect's psum reassociates sums, in float64 here
# (2e-9 to 8e-9 over the eight cases), where a wrong offset or a lane
# on the wrong row is 1e-1.
ONE_DEVICE_TOL = 1e-7


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("side", list(SIDES))
def test_ragged_glmix_on_mesh(baselines, mesh, side, layout):
    data, (one_model, one_scores), (ref_w, ref_scores) = baselines(side)
    cfg, n = config(), len(data["y"])
    assert n % CHIPS and n % 128
    coords = build(data, layout, mesh)
    for cid in ("per-user", "per-item"):
        assert layout_of(coords[cid]) == layout
        assert coords[cid]._use_soa == (side == "soa_newton")
        counts = np.bincount(data["id_tags"][coords[cid].config
                                             .random_effect_type])
        cap = coords[cid].config.active_cap
        assert counts.min() <= cap < counts.max()  # capped beside uncapped
        assert coords[cid].buckets.passive_rows > 0
    sweep = FusedSweep(coords, num_iterations=int(cfg["sweeps"]))
    model, scores = sweep.run()
    for cid in coords:
        if cid == "fixed":
            got = np.asarray(model[cid].coefficients.means)
            want, one = ref_w[cid], np.asarray(
                one_model[cid].coefficients.means)
        else:
            m = model[cid]
            assert sorted(m.slot_of) == sorted(ref_w[cid])  # EVERY entity
            assert m.slot_of == one_model[cid].slot_of
            got, want = np.asarray(m.w_stack), stacked(ref_w[cid], m)
            one = np.asarray(one_model[cid].w_stack)
        # every row, the passive ones too, and none of the padding
        assert scores[cid].shape == (n,)
        read = (relative(got, want), relative(scores[cid], ref_scores[cid]),
                relative(got, one), relative(scores[cid], one_scores[cid]))
        assert max(read[:2]) <= REFERENCE_TOL, (cid, read)
        assert max(read[2:]) <= ONE_DEVICE_TOL, (cid, read)


# -- (b) structure -------------------------------------------------------------

@pytest.fixture(scope="module")
def on_mesh(mesh):
    """The entity-major build (the benchmark cell's layout) under the
    mesh, traced: coordinates, sweep, compiled text, the tracer's records."""
    data = problem(SIDES["lbfgs"])
    prev = set_tracer(Tracer(capacity=8192, enabled=True))
    try:
        coords = build(data, "entity_major", mesh, dtype=np.float32)
        sweep = FusedSweep(coords, num_iterations=2)
        sweep.run_device()
        records = obs.get_tracer().records()
        table = obs.get_tracer().device_tables()["jit_program"]
    finally:
        set_tracer(prev)
    args, _ = sweep._program_args(None, None, 0, None)
    text = sweep._program.lower(*args).compile().as_text()
    return data, coords, sweep, text, records, table


def sharded_leaves(coords):
    for cid, coord in coords.items():
        for path, a in jax.tree_util.tree_leaves_with_path(
                coord.sweep_data()):
            if isinstance(a, jax.Array):
                yield cid + jax.tree_util.keystr(path), a


def test_every_sample_chunk_and_lane_axis_is_sharded(on_mesh):
    _, coords, sweep, *_ = on_mesh
    seen = 0
    leaves = list(sharded_leaves(coords)) + [
        ("carry" + jax.tree_util.keystr(p), a) for p, a in
        jax.tree_util.tree_leaves_with_path((sweep._cold, sweep._base))]
    for name, a in leaves:
        if a.ndim == 1 and a.shape[0] == 8:  # the fixed effect's state [d]
            continue
        if a.size == 0:  # ``rows`` of a class whose every lane is a run lane
            assert name.endswith("['rows']") and a.shape[0] == 0, name
            continue
        seen += 1
        assert len(a.sharding.device_set) == CHIPS, name
        local = a.sharding.shard_shape(a.shape)
        cut = [g // l for g, l in zip(a.shape, local)]
        assert sorted(cut) == [1] * (a.ndim - 1) + [CHIPS], (name, a.shape)
        assert max(s.data.nbytes for s in a.addressable_shards) \
            == a.nbytes // CHIPS, name
    assert seen > 60  # 14 classes x 5 arrays, the full-sample arrays, ...
    # the sample axis is whole (8, 128) tiles on every device, no more
    n = len(on_mesh[0]["y"])
    assert sweep._base.shape == (padded_samples(n, coords["fixed"].mesh),)
    assert coords["fixed"]._batch.x.shape[0] == n + (-n) % (CHIPS * 1024)


def collectives(text):
    return [(kind, shape) for shape, kind in COLLECTIVE.findall(text)]


def test_no_design_crosses_a_chip(on_mesh):
    _, coords, _, text, *_ = on_mesh
    found = collectives(text)
    kinds = {k for k, _ in found}
    assert "all-gather" in kinds and "all-reduce" in kinds
    # a design is an array with a feature axis beside a sample, chunk or
    # lane axis: [n, d], [d, R, 128], [lanes, rows, d]; whole or a shard
    designs = set()
    for name, a in sharded_leaves(coords):
        if a.ndim >= 2 and jnp.issubdtype(a.dtype, jnp.floating) and (
                name.endswith("x_em']") or name.endswith("['x']")
                or name.endswith(".x")):
            for shape in (a.shape, a.sharding.shard_shape(a.shape)):
                designs.add("[" + ",".join(map(str, shape)) + "]")
    assert len(designs) >= 30  # 1 + 2 + 14 designs, whole and a shard
    for kind, shape in found:
        assert not any(d in shape for d in designs), (kind, shape)
        # what crosses is a vector or a coefficient table
        dims = [list(map(int, m.split(","))) for m in
                re.findall(r"\[([\d,]+)\]", shape)]
        assert all(len(d) <= 2 for d in dims), (kind, shape)


def test_no_collective_and_no_exchange_without_chips_to_span():
    data = problem(SIDES["lbfgs"])
    assert spans_chips(None) is None
    one = make_mesh(devices=jax.devices()[:1])
    assert spans_chips(one) is None
    for mesh in (None, one):
        coords = build(data, "entity_major", mesh)
        sweep = FusedSweep(coords, num_iterations=2)
        args, _ = sweep._program_args(None, None, 0, None)
        text = sweep._program.lower(*args).compile().as_text()
        assert collectives(text) == []
        assert "photon.exchange" not in text
        assert all(c.exchange_bytes() == {} for c in coords.values())
        assert "photon.entity_gather" in text  # the one-chip program's scope


def test_fixed_design_stays_where_the_caller_put_it(mesh, monkeypatch):
    """Under a mesh a shard of many blocks whose rows do not divide is not
    padded to the kernels' block (fused_glm.runs_in_place, per shard): the
    design handed over in its shards IS the coordinate's buffer, and rows
    that do not divide by the devices are padded to the sample axis's next
    multiple only (whole tiles a device), wherever the design comes from."""
    from photon_ml_tpu.ops import fused_glm

    monkeypatch.setattr(fused_glm, "eligible",
                        lambda b, interpret=False: True)
    monkeypatch.setattr(fused_glm, "_IN_PLACE_BLOCKS", 2)
    d, block = 128, 2048
    assert fused_glm._pick_block_rows(10 ** 6, d, 4) == block
    n = CHIPS * (2 * block + 37) - 1          # divides by nothing
    n_pad = padded_samples(n, mesh)
    rng = np.random.default_rng(0)
    y = (rng.random(n) < 0.5).astype(np.float32)
    x_host = rng.normal(size=(n_pad, d)).astype(np.float32)
    x_host[n:] = 0
    x_dev = jax.device_put(x_host, jax.sharding.NamedSharding(
        mesh, over_chips(mesh, 2)))
    cfg = FixedEffectConfig(feature_shard="g", reg=Regularization(l2=1.0))

    def pointers(a):
        return [s.data.unsafe_buffer_pointer() for s in a.addressable_shards]

    for x in (x_dev, x_host[:n]):
        coord = build_coordinate("fixed", GameData(y=y, features={"g": x}),
                                 cfg, TASK, mesh)
        assert coord.num_samples == n and coord.carry_samples == n_pad
        assert coord._padded_n == n_pad  # not (2 x block + block) x CHIPS
        got = coord._batch
        assert got.x.sharding.shard_shape(got.x.shape) == (n_pad // CHIPS, d)
        np.testing.assert_array_equal(np.asarray(got.weight)[n:], 0)
        np.testing.assert_array_equal(np.asarray(got.weight)[:n], 1)
    assert pointers(got.x) != pointers(x_dev)  # from the host: its own
    coord = build_coordinate("fixed", GameData(y=y, features={"g": x_dev}),
                             cfg, TASK, mesh)
    assert pointers(coord._batch.x) == pointers(x_dev)  # no copy
    # a small design is still padded to a block a device, as it always was
    small = build_coordinate("fixed", GameData(
        y=y[:1000], features={"g": x_host[:1000]}), cfg, TASK, mesh)
    assert small._padded_n == padded_samples(1000, mesh) == CHIPS * 1024


@pytest.mark.parametrize("who", ["host_array", "one_device", "wrong_count",
                                 "no_mesh", "random_effect"])
def test_only_a_fixed_effect_under_the_mesh_takes_padding_rows(mesh, who):
    """More rows than labels are the sample axis's padding, and nothing
    else: a design in row shards over the mesh, of exactly
    ``padded_samples(n, mesh)`` rows, handed to the fixed effect."""
    n, d = 4099, 8
    n_pad = padded_samples(n, mesh)
    y = np.zeros(n, np.float32)
    rows = jax.sharding.NamedSharding(mesh, over_chips(mesh, 2))

    def data(x):
        return GameData(y=y, features={"g": x},
                        id_tags={"userId": np.arange(n) % 7})

    if who == "host_array":
        with pytest.raises(ValueError, match="expected 4099"):
            data(np.zeros((n_pad, d), np.float32))
        return
    if who == "one_device":
        with pytest.raises(ValueError, match="expected 4099"):
            data(jnp.zeros((n_pad, d), jnp.float32))
        return
    x = jax.device_put(np.zeros(
        (n_pad + CHIPS * (who == "wrong_count"), d), np.float32), rows)
    fixed = FixedEffectConfig(feature_shard="g", reg=Regularization(l2=1.0))
    if who == "random_effect":
        conf, under = RandomEffectConfig(
            random_effect_type="userId", feature_shard="g",
            reg=Regularization(l2=1.0)), mesh
    else:
        conf, under = fixed, None if who == "no_mesh" else mesh
    with pytest.raises(ValueError, match="expected 4099"):
        build_coordinate("c", data(x), conf, TASK, under)
    assert build_coordinate("c", data(x[:n_pad]), fixed, TASK,
                            mesh).carry_samples == n_pad


def test_kernels_run_a_shard_in_place_inside_shard_map(mesh, monkeypatch):
    """``ShardMapObjective`` over shards of two blocks and 37 rows: whole
    blocks under the main kernel where they lie, the last rows under
    ``fused_glm_tail_*`` (interpret mode: the kernels are TPU-only)."""
    import functools

    from photon_ml_tpu.core import losses
    from photon_ml_tpu.core.batch import DenseBatch
    from photon_ml_tpu.core.objective import GLMObjective
    from photon_ml_tpu.ops import fused_glm
    from photon_ml_tpu.parallel.fixed import ShardMapObjective
    from photon_ml_tpu.parallel.mesh import shard_batch

    monkeypatch.setattr(fused_glm, "eligible",
                        lambda b, interpret=False: isinstance(b, DenseBatch))
    monkeypatch.setattr(fused_glm, "_IN_PLACE_BLOCKS", 2)
    for name in ("fused_value_and_grad", "fused_hvp"):
        monkeypatch.setattr(fused_glm, name, functools.partial(
            getattr(fused_glm, name), interpret=True))
    d, local = 128, 2 * 2048 + 37
    n = CHIPS * local
    rng = np.random.default_rng(1)
    batch = DenseBatch(
        x=jnp.asarray(rng.normal(size=(n, d)) * 0.3),
        y=jnp.asarray((rng.random(n) < 0.5).astype(float)),
        offset=jnp.asarray(rng.normal(size=n) * 0.1),
        weight=jnp.asarray(rng.uniform(0.5, 2.0, size=n)))
    w = jnp.asarray(rng.normal(size=d) * 0.2)
    plain = GLMObjective(loss=losses.logistic_loss, reg=Regularization(l2=0.1))
    sm = ShardMapObjective(plain.replace(fused=True), mesh)
    sharded = shard_batch(batch, mesh)
    assert sharded.x.shape == batch.x.shape  # nothing padded
    jaxpr = str(jax.make_jaxpr(sm.value_and_grad)(w, sharded))
    assert "fused_glm_value_grad" in jaxpr
    assert "fused_glm_tail_value_grad" in jaxpr
    assert "photon.exchange.psum" in jax.jit(sm.value_and_grad).lower(
        w, sharded).as_text(debug_info=True)
    val, grad = jax.jit(sm.value_and_grad)(w, sharded)
    ref_val, ref_grad = plain.value_and_grad(w, batch)
    np.testing.assert_allclose(val, ref_val, rtol=1e-12)
    np.testing.assert_allclose(grad, ref_grad, rtol=1e-10, atol=1e-12)


def test_lanes_by_run_under_the_mesh(on_mesh):
    """ISSUE 31: per-user lanes whose rows are one run of samples are
    addressed by their start on every chip, the same number of them in
    every chip's share; ISSUE 35: so are the capped users' lanes by the
    start of the window their reservoir lies in, each chip holding its own
    lanes' picks; the lanes are the one-device lanes, entity by entity, and
    the offsets still cross in ONE all-gather an update."""
    data, coords, _, text, *_ = on_mesh
    n = len(data["y"])
    alone = build(data, "entity_major", None, dtype=np.float32)
    offsets = np.random.default_rng(5).normal(size=n).astype(np.float32)
    for cid in ("per-user", "per-item"):
        coord, one = coords[cid], alone[cid]
        classes = coord.buckets.buckets
        has_runs = [b.run_lanes > 0 for b in classes]
        # rows by user: runs; a movie's rows lie anywhere
        assert any(has_runs) == (cid == "per-user")
        assert ["run_start" in dev for dev in coord._dev] == has_runs
        has_windows = [b.window_lanes > 0 for b in classes]
        # the capped users' reservoirs lie inside windows; a movie's do not
        assert any(has_windows) == (cid == "per-user")
        assert ["windows" in dev for dev in coord._dev] == has_windows
        for b, dev in zip(classes, coord._dev):
            if b.run_lanes:
                assert dev["run_start"].shape == (CHIPS * b.run_lanes,)
                assert dev["run_start"].sharding.shard_shape(
                    dev["run_start"].shape) == (b.run_lanes,)
            if b.window_lanes:
                by = dev["windows"]
                assert by.start.shape == (CHIPS * b.window_lanes,)
                assert by.pull.shape == (CHIPS * b.window_lanes, by.window)
                for a in (by.start, by.pull):  # a chip holds its own lanes'
                    assert a.sharding.shard_shape(a.shape) == (
                        b.window_lanes,) + a.shape[1:]
            assert dev["rows"].shape == (
                b.num_lanes - CHIPS * (b.run_lanes + b.window_lanes),
                b.capacity)
            assert dev["valid"].shape == (b.num_lanes, b.capacity)
        gather = coord._offsets_into_lanes(
            samples_on_device(offsets, coord.mesh, np.float32), coord._dev)
        gather_one = one._offsets_into_lanes(jnp.asarray(offsets), one._dev)
        for bi, b in enumerate(classes):
            got, got_one = np.asarray(gather(bi)), np.asarray(gather_one(bi))
            assert np.array_equal(got, np.where(
                b.rows >= 0, offsets[np.maximum(b.rows, 0)], 0.0))
            for e, (bj, lane) in coord.buckets.lane_of.items():
                if bj == bi:
                    bk, lane_one = one.buckets.lane_of[e]
                    assert bk == bi
                    assert np.array_equal(got[lane], got_one[lane_one])
    crossing = [line for line in text.splitlines()
                if COLLECTIVE.match(line) and "photon.exchange.offsets" in line]
    assert len(crossing) == 2 and all("all-gather" in c for c in crossing)


def test_scores_come_back_by_unpad_under_the_mesh(on_mesh):
    """ISSUE 33: per-user rows arrive grouped by user, so each chip cuts
    the one range of the all-gathered entity-major scores that holds its
    samples and un-pads it: bitwise the one-device ``acc[pos]``, the padded
    sample axis exactly 0, no gather of one index a sample in the exchange;
    a movie's rows lie anywhere and keep the position gather."""
    data, coords, _, text, *_ = on_mesh
    n = len(data["y"])
    alone = build(data, "entity_major", None, dtype=np.float32)
    rng = np.random.default_rng(33)
    for cid, back in (("per-user", "unpad"), ("per-item", "gather")):
        coord, one = coords[cid], alone[cid]
        assert coord._em.back == one._em.back == back
        assert coord.carry_samples > n  # the sample axis is padded
        way = coord._full["way_back"]
        assert isinstance(way, bucketing.Unpad) == (back == "unpad")
        if back == "unpad":
            assert isinstance(one._full["way_back"], bucketing.Unpad)
            assert way.num_samples == coord.carry_samples // CHIPS
            assert way.start.shape == way.live.shape == (CHIPS,)
            assert int(np.asarray(way.live).sum()) == n
            assert way.pull.sharding.shard_shape(way.pull.shape) == (
                way.slots,)
            assert way.slots < coord._em.lanes * coord._em.chunk
        w = jnp.asarray(rng.normal(size=(len(coord._slot_of), coord.dim)),
                        jnp.float32)
        got = np.asarray(coord._score_samples_full(w, coord._full))
        want = np.asarray(bucketing.score_samples_em(
            w, one._full["lane_slot"], one._full["x_em"],
            jnp.asarray(one._em.pos)))  # the position gather, one device
        assert got.shape == (coord.carry_samples,) and want.shape == (n,)
        np.testing.assert_array_equal(got[:n].view(np.uint32),
                                      want.view(np.uint32))
        assert (got[n:] == 0).all() and np.abs(want).min() > 0
        np.testing.assert_array_equal(
            np.asarray(one._score_samples_full(w, one._full)), want)
        update = "photon.update." + cid.replace("-", "_")
        scoped = [line for line in text.splitlines()
                  if update + "/" in line and "photon.exchange.scores" in line]
        gathers = [line for line in scoped if re.search(r" gather\(", line)]
        assert bool(gathers) == (back == "gather"), gathers
        assert any("dynamic-slice(" in line for line in scoped) == (
            back == "unpad")
        assert any("all-gather" in line and COLLECTIVE.match(line)
                   for line in scoped)  # the exchange's one collective


def test_the_span_counts_one_coefficient_index_a_chunk(on_mesh):
    """``coord.rescore_layout``'s ``coef_indices``: the coefficient indices
    the entity-major scorer gathers a call, ONE row of the table a chunk,
    so the layout's lanes, under the mesh (every chip's chunks together)
    and on one device.  The two paths' scores are bitwise equal
    (``test_scores_come_back_by_unpad_under_the_mesh``)."""
    data, coords, _, _, records, _ = on_mesh
    prev = set_tracer(Tracer(capacity=1024, enabled=True))
    try:
        alone = build(data, "entity_major", None, dtype=np.float32)
        one = spans_named(obs.get_tracer().records(), "coord.rescore_layout")
    finally:
        set_tracer(prev)
    for spans, built in ((spans_named(records, "coord.rescore_layout"),
                          coords), (one, alone)):
        em = {a["coordinate"]: a for a in spans
              if a["layout"] == "entity_major"}
        assert sorted(em) == ["per-item", "per-user"]
        for cid, a in em.items():
            assert a["coef_indices"] == a["lanes"] == built[cid]._em.lanes
            assert built[cid]._full["lane_slot"].size == a["lanes"]


# -- (c) tracing ---------------------------------------------------------------

def test_exchange_scopes_are_in_the_op_table(on_mesh):
    *_, table = on_mesh
    seen = {}
    for path in table.values():
        kind = exchange_model.exchange_kind(path)
        if kind is not None:
            cid = next((p for p in path.split("/")
                        if p.startswith("photon.update.")), "the end")
            seen.setdefault(cid, set()).add(kind)
    assert seen["photon.update.fixed"] == {"psum"}
    assert seen["the end"] == {"publish"}  # the fit's published tables
    for cid in ("photon.update.per_user", "photon.update.per_item"):
        assert seen[cid] == {"offsets", "publish", "scores", "counts"}
    # the local half of the offsets exchange keeps its layer's name
    assert any("photon.exchange.offsets/photon.entity_gather" in p
               for p in table.values())


def spans_named(records, name):
    return [r["attrs"] for r in records
            if r["ph"] == "X" and r["name"] == name]


def test_spans_say_what_is_sharded_and_what_crosses(on_mesh):
    data, coords, sweep, _, records, _ = on_mesh
    n = len(data["y"])
    uploads = spans_named(records, "coord.upload")
    assert len(uploads) == 1 + 2 * 2
    for a in uploads:
        assert a["devices"] == CHIPS
        assert a["bytes_sharded"] > 0 and a["bytes_replicated"] == 0
    held = sum(a.nbytes for _, a in sharded_leaves(coords))
    slot_idx = sum(a.nbytes for c in ("per-user", "per-item")
                   for a in coords[c]._slot_idx_dev)
    assert sum(a["bytes_sharded"] for a in uploads) == held + slot_idx
    for a in spans_named(records, "coord.bucket"):
        assert a["lanes_per_device"] == [l // CHIPS for l in a["lanes"]]
        assert all(l % CHIPS == 0 for l in a["lanes"])
        # ISSUE 31: the lanes addressed by their run's start, a multiple
        # of the chips in every class; none of a movie's
        assert all(r % CHIPS == 0 for r in a["run_lanes"])
        assert (a["run_slots"] > 0) == (a["coordinate"] == "per-user")
        # ISSUE 35: and those addressed by their window's start
        assert all(w % CHIPS == 0 for w in a["window_lanes"])
        assert (a["window_slots"] > 0) == (a["coordinate"] == "per-user")
        assert (a["run_slots"] + a["window_slots"] + a["index_slots"]
                == a["slots"])
    (ex,) = spans_named(records, "descent.exchange")
    assert ex["coordinates"] == list(coords) and ex["devices"] == CHIPS
    assert ex["collectives"] == sweep._collectives != {}
    sent = dict(zip(ex["coordinates"], ex["bytes_sent"]))
    n_pad = padded_samples(n, coords["fixed"].mesh)
    assert sent["fixed"] == {"psum": 2 * 3 * (8 + 2) * 4 // CHIPS}
    for cid in ("per-user", "per-item"):
        em = coords[cid]._em
        assert sent[cid]["offsets"] == 2 * 3 * n_pad * 4 // CHIPS
        assert sent[cid]["scores"] == 2 * 3 * em.lanes * em.chunk * 4 // CHIPS
        assert sent[cid]["publish"] == 2 * 2 * 3 * len(
            coords[cid]._sorted_ids) * SIDES["lbfgs"] * 4 // CHIPS
    # the benchmark's model of what MUST cross against what the program
    # says its all-gathers send: the same vectors, times the chips
    # (the program's vectors are padded to whole tiles a device: 33% at
    # this size, 0.008% at the cell's)
    must = exchange_model.must_send_bytes(config(), CHIPS, n)
    padded = exchange_model.must_send_bytes(config(), CHIPS, n_pad)
    program = sum(sent[c]["offsets"] for c in ("per-user", "per-item"))
    assert must["offsets"] == must["scores"] < padded["offsets"]
    assert padded["offsets"] * CHIPS == program
    # one chip: no span, nothing to send
    one = FusedSweep(build(data, "row_major", None), num_iterations=1)
    prev = set_tracer(Tracer(capacity=1024, enabled=True))
    try:
        one.run_device()
        assert not spans_named(obs.get_tracer().records(), "descent.exchange")
        assert spans_named(obs.get_tracer().records(),
                           "descent.solve_iterations")
    finally:
        set_tracer(prev)


READERS = ("xchip_collective_busy_share", "xchip_exchange_busy_share",
           "xchip_exchange_ici_share", "xchip_replicated_bytes_share",
           "xchip_fixed_passes_per_fit", "xchip_fused_glm_hbm_share")


def read(name, readings):
    return CATALOG.module("layer_metrics", name).read(readings)


def test_readers_on_a_synthetic_trace(on_mesh):
    """Seconds charged by instruction name, as ``trace_reduce`` hands them
    over: 1 ms a collective on each of four chips, 2 ms an instruction
    under an exchange scope that is none, 10 ms of kernel."""
    *_, records, table = on_mesh
    _, _, sweep, text, *_ = on_mesh
    from photon_ml_tpu.obs.trace import hlo_collectives

    named = hlo_collectives(text)
    assert named == sweep._collectives
    assert {"all-gather", "all-reduce"} <= set(named.values())
    gathers = [n for n, k in named.items() if k == "all-gather"
               and "photon.exchange.offsets" in table[n]]
    local = [n for n, p in table.items() if "photon.entity_gather" in p
             and n not in named]
    assert gathers and local
    # a collective the program names and gives no scope (the TPU compiler
    # rewrites a small all-gather as an all-reduce with no metadata)
    named = dict(named, **{"all-reduce.77": "all-reduce"})
    ops = {gathers[0]: [4e6, 8], "all-reduce.77": [2e6, 4],
           local[0]: [8e6, 8], "fused_glm_value_grad.1": [40e6, 44],
           "fusion.9999": [46e6, 4]}
    readings = {"profile": {"ops_self": ops, "busy_s": 0.025, "chips": 4,
                            "window_s": 0.03},
                "measured": {"slice_fits": 2}, "config": config(),
                "chips": CHIPS, "device": {"kind": "TPU v5 lite"}}
    tracer = Tracer(capacity=64, enabled=True)
    tracer.record_device_table("jit_program", table)
    with tracer.span("descent.exchange", devices=CHIPS, collectives=named):
        pass
    with tracer.span("coord.upload", bytes_sharded=990, bytes_replicated=10,
                     devices=CHIPS):
        pass
    prev = set_tracer(tracer)
    try:
        assert read(READERS[0], readings) == pytest.approx(100 * 6e-3 / 0.1)
        # the scoped all-gather and the local gather; the unscoped
        # all-reduce is a collective and no exchange
        assert read(READERS[1], readings) == pytest.approx(100 * 12e-3 / 0.1)
        must = exchange_model.must_send_bytes(config(), CHIPS, 9217,
                                              fixed_evaluations=44 / 4 / 2)
        assert read(READERS[2], readings) == pytest.approx(
            100 * sum(must.values()) * 2 / (6e-3 / 4 * 200e9))
        assert read(READERS[3], readings) == pytest.approx(1.0)
        # the kernels a CHIP: 44 calls over four chips and two fits; a call
        # over a quarter of the rows (roofline.fused_glm_call), in 40 ms
        assert read(READERS[4], readings) == pytest.approx(44 / 4 / 2)
        shard, d = -(-9217 // CHIPS), config()["coordinates"][0]["dim"]
        moved = 44 * (shard * d * 4 + 3 * shard * 4 + 16 * d * 4)
        assert read(READERS[5], readings) == pytest.approx(
            100 * moved / 40e-3 / 819e9)
        # nothing to read: no device trace (a CPU run)
        for name in READERS[:3] + READERS[4:]:
            assert read(name, dict(readings, profile=None)) is None
        # a one-chip slice: no collective ran, nothing is under an exchange,
        # no kernel
        quiet = dict(readings, profile=dict(
            readings["profile"], ops_self={"fusion.9999": [1e6, 1]}))
        for name in READERS[:3] + READERS[4:]:
            assert read(name, quiet) is None
    finally:
        set_tracer(prev)
    # the parent's program: no table, no such span, no such attributes
    bare = Tracer(capacity=64, enabled=True)
    with bare.span("coord.upload", bytes=5):
        pass
    prev = set_tracer(bare)
    try:
        for name in READERS[:4]:
            assert read(name, readings) is None
    finally:
        set_tracer(prev)


def test_must_send_bytes_of_the_cell():
    """The issue's reckoning at full size: (chips - 1) / chips of n / chips
    rows x 4 B each way, per random effect and sweep."""
    cfg = CATALOG.json("configs", "glmix_ml25m")
    must = exchange_model.must_send_bytes(cfg, 4, 25_000_095, 11.0)
    assert must["offsets"] == 2 * 2 * 0.75 * 6_250_024 * 4 == 75_000_288
    assert must["psum"] == 11 * 2 * 0.75 * 130 * 4
    assert exchange_model.rows_of(cfg) == 25_000_095


PLANTED = (  # the fixed effect's all-reduce left out: every chip its own fit
    "import sys; sys.path[:0] = [{bench!r}, {repo!r}]; import run; "
    "from photon_ml_tpu.parallel.fixed import ShardMapObjective; "
    "ShardMapObjective._psum = lambda self, tree: tree; "
    "sys.exit(run.main(sys.argv[1:]))")


@pytest.mark.parametrize("trace,planted", [(0, False), (1, False), (0, True)],
                         ids=["untraced", "traced", "psum_left_out"])
def test_cell_dry_run_on_four_virtual_devices(trace, planted):
    """The benchmark's own command at the cell's dry-run sizes: the recipe's
    hand-over in shards, the normal path, the mix's checks on sharded
    arrays, the readers.  With the exchange between chips planted out of
    the fixed effect the run is not ``correct``, by the mix's check of the
    fixed rows of every chip and by no other."""
    program = (["-c", PLANTED.format(bench=BENCH, repo=REPO)] if planted
               else [os.path.join(BENCH, "run.py")])
    done = subprocess.run(
        [sys.executable, *program, "--workload", CELL,
         "--seed", "2147483659", "--seconds", "2", "--trace", str(trace),
         "--dry-run"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    wl = CATALOG.json("workloads", CELL)
    assert wl["chips"] == CHIPS and wl["traffic"] == "train_fits_sharded"
    by_part = line["detail"]["fixed_rows_err_by_part"]
    assert len(by_part) == CHIPS and line["detail"]["fixed_rows_checked"] > 64
    if planted:
        assert line["correct"] is False
        assert [k for k, ok in line["checks"].items() if not ok] == [
            "fixed_rows_scored"]
        # the published coefficients are the first chip's: its rows agree
        assert by_part[0] <= wl["gates"]["fixed_score_tol"]
        assert min(by_part[1:]) > 1000 * wl["gates"]["fixed_score_tol"]
        return
    assert line["dry_run"] is True and line["correct"] is True, line["checks"]
    assert max(by_part) <= wl["gates"]["fixed_score_tol"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["count"] >= CHIPS
    assert line["checks"]["no_compile_in_window"]
    assert line["detail"]["rows"] == 9217
    if not trace:
        assert set(line["metrics"]) == {"train_examples_per_s", "setup_s"}
        return
    want = {m for m in wl["per_layer"] if CATALOG.json(
        "layer_metrics", m)["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    assert "xchip_replicated_bytes_share" in want
    assert line["metrics"]["xchip_replicated_bytes_share"]["value"] < 1.0
