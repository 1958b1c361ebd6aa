"""Sparse feature shards: row-padded COO end-to-end (the huge-vocabulary
path — reference scale story, SURVEY §2.7)."""

import json
import os

import numpy as np
import pytest

from photon_ml_tpu.core.regularization import Regularization
from photon_ml_tpu.data import avro as avro_io
from photon_ml_tpu.data.index_map import build_index_maps_from_avro
from photon_ml_tpu.data.reader import read_game_data_avro
from photon_ml_tpu.data.schemas import TRAINING_EXAMPLE
from photon_ml_tpu.game.config import FixedEffectConfig, GameConfig
from photon_ml_tpu.game.data import SparseShard
from photon_ml_tpu.game.estimator import GameEstimator
from photon_ml_tpu.types import TaskType


def _write(path, n=300, vocab=40, k=5, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=vocab) * 0.7
    records = []
    for i in range(n):
        js = rng.choice(vocab, size=k, replace=False)
        vs = rng.normal(size=k)
        logit = float(vs @ w[js])
        yv = float(rng.random() < 1 / (1 + np.exp(-logit)))
        feats = [{"name": f"f{j}", "term": "", "value": float(v)}
                 for j, v in zip(js, vs)]
        records.append({"uid": i, "response": yv, "label": None,
                        "features": feats, "weight": None, "offset": None,
                        "metadataMap": {}})
    avro_io.write_container(path, TRAINING_EXAMPLE, records)


@pytest.fixture(scope="module")
def sparse_setup(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("sp") / "train.avro")
    _write(path)
    imap = build_index_maps_from_avro([path], {"all": []})["all"]
    return path, imap


def test_sparse_load_layout(sparse_setup):
    path, imap = sparse_setup
    data, _ = read_game_data_avro([path], {"all": imap}, sparse_shards={"all"})
    shard = data.features["all"]
    assert isinstance(shard, SparseShard)
    n, d = shard.shape
    assert n == 300 and d == imap.size
    assert shard.indices.shape == shard.values.shape
    assert shard.indices.shape[1] <= 5 + 1  # k features + intercept slot
    # intercept slot present on every row
    ii = imap.intercept_index
    assert np.all(np.any((shard.indices == ii) & (shard.values == 1.0), axis=1))


def test_sparse_dense_margin_parity(sparse_setup):
    path, imap = sparse_setup
    dense, _ = read_game_data_avro([path], {"all": imap})
    sparse, _ = read_game_data_avro([path], {"all": imap}, sparse_shards={"all"})
    w = np.random.default_rng(1).normal(size=imap.size)
    dense_margins = np.asarray(dense.features["all"]) @ w
    sh = sparse.features["all"]
    sparse_margins = np.einsum("nk,nk->n", sh.values, w[sh.indices])
    np.testing.assert_allclose(sparse_margins, dense_margins, rtol=1e-5)


@pytest.mark.parametrize("opt", ["LBFGS", "TRON"])
def test_sparse_dense_solve_parity(sparse_setup, opt):
    """The fixed-effect solve must reach the same optimum either layout."""
    from photon_ml_tpu.types import OptimizerType

    path, imap = sparse_setup
    cfg = GameConfig(task=TaskType.LOGISTIC_REGRESSION, coordinates={
        "fixed": FixedEffectConfig(feature_shard="all",
                                   optimizer=OptimizerType[opt],
                                   reg=Regularization(l2=0.1))})
    out = {}
    for mode, sparse_set in (("dense", set()), ("sparse", {"all"})):
        data, _ = read_game_data_avro([path], {"all": imap},
                                      sparse_shards=sparse_set)
        res = GameEstimator().fit(data, [cfg])[0]
        out[mode] = np.asarray(res.model["fixed"].coefficients.means)
    # different computation orders (matmul vs gather/scatter) -> optima agree
    # only to solver-tolerance scale in f32; the approximate-Wolfe slack
    # (opt/linesearch.py) lets each stop anywhere in the working-precision
    # plateau, so ill-conditioned coordinates wander a few e-3 at equal
    # objective value
    np.testing.assert_allclose(out["sparse"], out["dense"], atol=5e-3)


def test_sparse_fallback_records_path(sparse_setup, monkeypatch):
    """The Python-codec fallback builds the same SparseShard."""
    import photon_ml_tpu.data.native_avro as na

    path, imap = sparse_setup
    fast, _ = read_game_data_avro([path], {"all": imap}, sparse_shards={"all"})
    monkeypatch.setattr(na, "_lib", None)
    monkeypatch.setattr(na, "_lib_tried", True)
    slow, _ = read_game_data_avro([path], {"all": imap}, sparse_shards={"all"})
    f, s = fast.features["all"], slow.features["all"]
    assert isinstance(s, SparseShard)
    w = np.random.default_rng(2).normal(size=imap.size)
    np.testing.assert_allclose(np.einsum("nk,nk->n", f.values, w[f.indices]),
                               np.einsum("nk,nk->n", s.values, w[s.indices]),
                               rtol=1e-5)


def test_huge_vocab_memory(tmp_path):
    """100k-feature shard: sparse layout is O(n*k); dense would be 4.8GB at
    this n — the load itself is the test."""
    path = str(tmp_path / "wide.avro")
    n, vocab = 1200, 100_000
    _write(path, n=n, vocab=vocab, k=8, seed=3)
    imap = build_index_maps_from_avro([path], {"all": []})["all"]
    assert imap.size == vocab + 1 or imap.size > 8  # observed features + intercept
    data, _ = read_game_data_avro([path], {"all": imap}, sparse_shards={"all"})
    shard = data.features["all"]
    assert isinstance(shard, SparseShard)
    assert shard.values.nbytes < 10 * n * 16  # O(n*k), nowhere near n*d

    cfg = GameConfig(task=TaskType.LOGISTIC_REGRESSION, coordinates={
        "fixed": FixedEffectConfig(feature_shard="all", reg=Regularization(l2=1.0))})
    res = GameEstimator().fit(data, [cfg])[0]
    w = np.asarray(res.model["fixed"].coefficients.means)
    assert w.shape == (shard.dim,) and np.all(np.isfinite(w))


def test_sparse_cli_end_to_end(tmp_path):
    from photon_ml_tpu.cli import score as score_cli
    from photon_ml_tpu.cli import train as train_cli

    train_path = str(tmp_path / "train.avro")
    _write(train_path, n=400, vocab=60, seed=4)
    out = str(tmp_path / "out")
    rc = train_cli.run([
        "--train-data", train_path, "--validation-data", train_path,
        "--feature-shards", "all", "--evaluators", "auc",
        "--coordinate", "name=fixed,feature.shard=all,reg.weights=0.1",
        "--sparse-threshold", "10",  # vocab 60 > 10 -> sparse
        "--output-dir", out])
    assert rc == 0
    summary = json.load(open(os.path.join(out, "training-summary.json")))
    assert summary["validation"]["auc"] > 0.6

    score_out = str(tmp_path / "scores")
    rc = score_cli.run(["--data", train_path, "--model-dir", out,
                        "--output-dir", score_out, "--evaluators", "auc"])
    assert rc == 0
    assert json.load(open(os.path.join(score_out, "metrics.json")))["auc"] > 0.6


def test_sparse_feature_sharded_estimator_parity(sparse_setup):
    """feature.sharded through the estimator on a (data=2, feature=4) mesh:
    blocked-w solve must match the replicated-w solve (the CLI-reachable form
    of the 1M-vocabulary scale path)."""
    import jax

    from photon_ml_tpu.parallel.mesh import make_mesh

    path, imap = sparse_setup
    data, _ = read_game_data_avro([path], {"all": imap}, sparse_shards={"all"})

    def fit(cfg, mesh=None):
        res = GameEstimator(mesh=mesh).fit(data, [cfg])[0]
        return np.asarray(res.model["fixed"].coefficients.means)

    base = FixedEffectConfig(feature_shard="all", reg=Regularization(l2=0.5))
    plain = fit(GameConfig(task=TaskType.LOGISTIC_REGRESSION,
                           coordinates={"fixed": base}))
    mesh = make_mesh(n_data=2, n_feature=4, devices=jax.devices())
    sharded_cfg = GameConfig(task=TaskType.LOGISTIC_REGRESSION, coordinates={
        "fixed": FixedEffectConfig(feature_shard="all", reg=Regularization(l2=0.5),
                                   feature_sharded=True)})
    sharded = fit(sharded_cfg, mesh)
    assert sharded.shape == plain.shape  # padding trimmed
    np.testing.assert_allclose(sharded, plain, atol=2e-3)


def test_sparse_feature_sharded_cli(tmp_path):
    """--mesh feature=4 + feature.sharded=true end-to-end through the CLI."""
    from photon_ml_tpu.cli import train as train_cli

    train_path = str(tmp_path / "train.avro")
    _write(train_path, n=400, vocab=60, seed=5)
    out = str(tmp_path / "out")
    rc = train_cli.run([
        "--train-data", train_path, "--validation-data", train_path,
        "--feature-shards", "all", "--evaluators", "auc",
        "--coordinate",
        "name=fixed,feature.shard=all,reg.weights=0.1,feature.sharded=true",
        "--sparse-threshold", "10",
        "--mesh", "data=2,feature=4",
        "--output-dir", out])
    assert rc == 0
    summary = json.load(open(os.path.join(out, "training-summary.json")))
    assert summary["validation"]["auc"] > 0.6


def test_sparse_re_round4_combos_cli(tmp_path):
    """The round-4 carve-outs are CLI-REACHABLE on sparse shards: the train
    driver no longer forces dense for FULL variances, RANDOM projection, or
    STANDARDIZATION on random-effect coordinates (game/coordinate supports
    them under compaction since round 4)."""
    import json as _json

    from photon_ml_tpu.cli import train as train_cli

    rng = np.random.default_rng(9)
    path = str(tmp_path / "train.avro")
    n, vocab, k, n_users = 400, 60, 5, 10
    w = rng.normal(size=vocab) * 0.7
    records = []
    for i in range(n):
        js = rng.choice(vocab, size=k, replace=False)
        vs = rng.normal(size=k)
        yv = float(rng.random() < 1 / (1 + np.exp(-float(vs @ w[js]))))
        records.append({"uid": i, "response": yv, "label": None,
                        "features": [{"name": f"f{j}", "term": "",
                                      "value": float(v)}
                                     for j, v in zip(js, vs)],
                        "weight": None, "offset": None,
                        "metadataMap": {"userId": str(i % n_users)}})
    avro_io.write_container(path, TRAINING_EXAMPLE, records)

    cases = {
        "full_var": "name=user,random.effect.type=userId,feature.shard=all,"
                    "reg.weights=1,variance.type=FULL",
        "random_proj": "name=user,random.effect.type=userId,"
                       "feature.shard=all,reg.weights=1,projector=RANDOM,"
                       "projected.dim=4",
    }
    for label, coord in cases.items():
        out = str(tmp_path / label)
        rc = train_cli.run([
            "--train-data", path, "--validation-data", path,
            "--feature-shards", "all", "--evaluators", "auc",
            "--id-tags", "userId",
            "--coordinate", coord,
            "--sparse-threshold", "10",  # vocab 60 > 10 -> sparse
            "--output-dir", out])
        assert rc == 0, label
        summary = _json.load(open(os.path.join(out, "training-summary.json")))
        assert summary["validation"]["auc"] > 0.5, label

    # STANDARDIZATION over a sparse RE shard (per-lane projected contexts;
    # the intercept id auto-fills from the index map)
    out = str(tmp_path / "standardized")
    rc = train_cli.run([
        "--train-data", path, "--validation-data", path,
        "--feature-shards", "all", "--evaluators", "auc",
        "--id-tags", "userId",
        "--normalization", "STANDARDIZATION",
        "--coordinate",
        "name=fixed,feature.shard=all,reg.weights=0.1",
        "--coordinate",
        "name=user,random.effect.type=userId,feature.shard=all,"
        "reg.weights=1",
        "--sparse-threshold", "10",
        "--output-dir", out])
    assert rc == 0
    summary = _json.load(open(os.path.join(out, "training-summary.json")))
    assert summary["validation"]["auc"] > 0.6


def test_sparse_feature_sharded_bf16_storage():
    """feature.sharded x sparse x bf16 storage compose: the blocked-w
    sharded objective reads storage-width values and widens in-register
    (ShardSparseObjective._local_margins vals.astype(blk.dtype)), so the
    solve tracks the f32 twin to bf16 input resolution."""
    import jax

    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(5)
    n, d, k = 512, 97, 6
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    w = rng.normal(size=d) * 0.5
    y = (rng.random(n) < 1 / (1 + np.exp(
        -np.einsum("nk,nk->n", vals, w[idx])))).astype(np.float32)
    from photon_ml_tpu.game.data import GameData

    gd = GameData(y=y, features={"g": SparseShard(indices=idx, values=vals,
                                                  dim=d)})
    mesh = make_mesh(n_data=2, n_feature=4, devices=jax.devices())
    out = {}
    from photon_ml_tpu.game.coordinate import build_coordinate

    for sd in (None, "bfloat16"):
        cfg = FixedEffectConfig(feature_shard="g",
                                solver=__import__("photon_ml_tpu.opt.types",
                                                  fromlist=["SolverConfig"]
                                                  ).SolverConfig(max_iters=40),
                                reg=Regularization(l2=0.5),
                                feature_sharded=True, storage_dtype=sd)
        c = build_coordinate("fixed", gd, cfg, TaskType.LOGISTIC_REGRESSION,
                             mesh)
        m, _ = c.update(np.zeros(n, np.float32))
        out[sd or "f32"] = np.asarray(m.coefficients.means)
        assert out[sd or "f32"].shape == (d,)
        assert np.all(np.isfinite(out[sd or "f32"]))
    np.testing.assert_allclose(out["bfloat16"], out["f32"], atol=1.5e-2)


def test_sparse_feature_sharded_fused_sweep_matches_host():
    """A fused sweep CONTAINING a feature.sharded=true coordinate: the
    coordinate's state stays P("feature")-sharded [d_pad] inside the scanned
    program and the residual fold consumes its feature-axis-reduced [n]
    scores.  Must match the host-paced loop on the same coordinates, be
    invariant to the mesh factorization (chip-count invariance), and agree
    with the replicated-w fused sweep — one descent path for every model
    size, like the reference (CoordinateDescent.scala:93-107)."""
    import jax

    from photon_ml_tpu.game import CoordinateDescent
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.game.config import RandomEffectConfig
    from photon_ml_tpu.game.data import GameData
    from photon_ml_tpu.game.fused import FusedSweep
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(7)
    # vocab 97 is NOT a multiple of any feature-axis size used below, so the
    # padded-slot trim on publish is exercised
    n, d, k, n_users = 768, 97, 6, 16
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    xu = rng.normal(size=(n, 3)).astype(np.float32)
    uids = np.repeat(np.arange(n_users), n // n_users)
    w = rng.normal(size=d) * 0.5
    wu = rng.normal(size=(n_users, 3)) * 0.8
    logit = (np.einsum("nk,nk->n", vals, w[idx])
             + np.einsum("nd,nd->n", xu, wu[uids]))
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    data = GameData(y=y,
                    features={"g": SparseShard(indices=idx, values=vals, dim=d),
                              "u": xu},
                    id_tags={"userId": uids})
    solver = SolverConfig(max_iters=30)

    def coords(mesh):
        cfgs = {
            "fixed": FixedEffectConfig(feature_shard="g", solver=solver,
                                       reg=Regularization(l2=1.0),
                                       feature_sharded=mesh is not None),
            "per-user": RandomEffectConfig(random_effect_type="userId",
                                           feature_shard="u", solver=solver,
                                           reg=Regularization(l2=1.0)),
        }
        return {cid: build_coordinate(cid, data, c,
                                      TaskType.LOGISTIC_REGRESSION, mesh)
                for cid, c in cfgs.items()}

    mesh = make_mesh(n_data=2, n_feature=4, devices=jax.devices())
    cs = coords(mesh)
    fused_model, fused_scores = FusedSweep(cs, num_iterations=2).run()
    host_model, _, _ = CoordinateDescent(cs, num_iterations=2).run()

    wf = np.asarray(fused_model["fixed"].coefficients.means)
    assert wf.shape == (d,)  # padded slots trimmed on publish
    np.testing.assert_allclose(wf, host_model["fixed"].coefficients.means,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(fused_model["per-user"].w_stack,
                               host_model["per-user"].w_stack,
                               rtol=2e-3, atol=2e-3)
    # the in-program sharded re-scoring equals the model's own re-scoring
    np.testing.assert_allclose(
        fused_scores["fixed"],
        np.asarray(cs["fixed"].score(fused_model["fixed"])),
        rtol=1e-5, atol=1e-5)

    # chip-count invariance: a different mesh factorization, same optimum
    mesh2 = make_mesh(n_data=4, n_feature=2, devices=jax.devices())
    alt_model, _ = FusedSweep(coords(mesh2), num_iterations=2).run()
    np.testing.assert_allclose(alt_model["fixed"].coefficients.means, wf,
                               atol=2e-3)

    # replicated-w fused sweep (no mesh) reaches the same optimum
    rep_model, _ = FusedSweep(coords(None), num_iterations=2).run()
    np.testing.assert_allclose(rep_model["fixed"].coefficients.means, wf,
                               atol=2e-3)


# ---------------------------------------------------------------------------
# Sparse per-entity random-effect shards (reference LocalDataset holds sparse
# Breeze vectors per entity, data/LocalDataset.scala:35-247 — wide sparse RE
# feature bags must train WITHOUT densifying to the vocabulary)
# ---------------------------------------------------------------------------

def _sparse_re_data(seed=5, n=1024, d=2048, k=8, n_users=32):
    """Row-sparse per-user bag + its densified twin (same samples)."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    vals[rng.random((n, k)) < 0.2] = 0.0  # padded COO slots (value 0)
    uids = np.repeat(np.arange(n_users), n // n_users)
    rng.shuffle(uids)
    w_true = (rng.normal(size=(n_users, d)) * 0.3).astype(np.float32)
    margins = np.array([vals[i] @ w_true[uids[i], idx[i]] for i in range(n)])
    y = (rng.random(n) < 1 / (1 + np.exp(-margins))).astype(np.float32)
    dense = np.zeros((n, d), np.float32)
    np.add.at(dense, (np.repeat(np.arange(n), k), idx.ravel()), vals.ravel())
    return idx, vals, dense, uids, y, d


def _re_coordinate(features, uids, y, d, norm=None, **cfg_kw):
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.game.data import GameData
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.game.config import RandomEffectConfig

    cfg = RandomEffectConfig(random_effect_type="userId", feature_shard="u",
                             solver=SolverConfig(max_iters=25),
                             reg=Regularization(l2=1.0), **cfg_kw)
    gd = GameData(y=y, features={"u": features}, id_tags={"userId": uids})
    return build_coordinate("u", gd, cfg, TaskType.LOGISTIC_REGRESSION,
                            norm=norm), gd


def test_sparse_re_parity_vs_densified_and_hbm():
    """Sparse-shard RE fit == densified-shard RE fit (coefficients, scores),
    with the compact bucket design blocks a small fraction of the densified
    ones (the HBM claim: observed-columns width, not vocabulary width)."""
    idx, vals, dense, uids, y, d = _sparse_re_data()
    cs, _ = _re_coordinate(SparseShard(indices=idx, values=vals, dim=d),
                           uids, y, d)
    cd, _ = _re_coordinate(dense, uids, y, d)
    off = np.zeros(len(y), np.float32)
    ms, _ = cs.update(off)
    md, _ = cd.update(off)
    assert ms.w_stack.shape == md.w_stack.shape == (32, d)
    np.testing.assert_allclose(ms.w_stack, md.w_stack, atol=5e-4)
    np.testing.assert_allclose(cs.score(ms), cd.score(md), atol=5e-3)
    sparse_bytes = sum(b.x.nbytes for b in cs.buckets.buckets)
    dense_bytes = sum(b.x.nbytes for b in cd.buckets.buckets)
    # 1024 rows * 8 nnz / 32 users -> <=256 observed columns vs d=2048:
    # compact blocks must be at least 4x smaller here (8x at these shapes)
    assert dense_bytes >= 4 * sparse_bytes, (sparse_bytes, dense_bytes)


def test_sparse_re_fused_sweep_matches_host():
    """A GAME descent (fixed + sparse RE) agrees between the fused program
    and the host loop, and validation scoring consumes the sparse shard."""
    from photon_ml_tpu.evaluation import EvaluationSuite
    from photon_ml_tpu.game import CoordinateDescent
    from photon_ml_tpu.game.data import GameData
    from photon_ml_tpu.game.estimator import GameEstimator, GameTransformer
    from photon_ml_tpu.game.fused import FusedSweep
    from photon_ml_tpu.opt.types import SolverConfig

    idx, vals, dense, uids, y, d = _sparse_re_data(n=1024, d=1024, n_users=32)
    rng = np.random.default_rng(0)
    xg = rng.normal(size=(len(y), 8)).astype(np.float32)
    cut = 768
    def gd(sl):
        return GameData(y=y[sl], features={
            "g": xg[sl],
            "u": SparseShard(indices=idx[sl], values=vals[sl], dim=d)},
            id_tags={"userId": uids[sl]})
    tr, va = gd(slice(None, cut)), gd(slice(cut, None))
    solver = SolverConfig(max_iters=25)
    config = GameConfig(
        task=TaskType.LOGISTIC_REGRESSION, num_outer_iterations=2,
        coordinates={
            "fixed": FixedEffectConfig(feature_shard="g", solver=solver,
                                       reg=Regularization(l2=1.0)),
            "per-user": __import__("photon_ml_tpu.game.config", fromlist=["RandomEffectConfig"]).RandomEffectConfig(
                random_effect_type="userId", feature_shard="u", solver=solver,
                reg=Regularization(l2=1.0))})
    est = GameEstimator(validation_suite=EvaluationSuite.from_specs(["auc"]))
    coords = {cid: est.build_one_coordinate(cid, tr, c, config.task, 0)
              for cid, c in config.coordinates.items()}
    model_f, _ = FusedSweep(coords, num_iterations=2).run()
    model_h, _, _ = CoordinateDescent(coords, num_iterations=2).run()
    suite = est.validation_suite
    auc_f = GameTransformer(model_f, config.task).evaluate(va, suite).values["auc"]
    auc_h = GameTransformer(model_h, config.task).evaluate(va, suite).values["auc"]
    assert abs(auc_f - auc_h) < 2e-3
    # fused and host run the same math but reassociate float32 reductions
    # differently, and 25 warm-started solver iterations amplify the last
    # bits — coefficients agree to ~1e-3, the AUC guard above is the
    # functional check
    np.testing.assert_allclose(model_f["per-user"].w_stack,
                               model_h["per-user"].w_stack, atol=5e-3)


def test_sparse_re_pearson_ratio_and_normalization():
    """INDEX_MAP + features_to_samples_ratio prunes each entity to its top-k
    |Pearson| observed columns (intercept pinned); factor normalization rides
    the per-lane compact space and round-trips to original-space models."""
    from photon_ml_tpu.core.normalization import NormalizationContext
    from photon_ml_tpu.game.coordinate import build_coordinate
    from photon_ml_tpu.game.config import RandomEffectConfig
    from photon_ml_tpu.game.data import GameData
    from photon_ml_tpu.opt.types import SolverConfig
    from photon_ml_tpu.types import ProjectorType

    # small vocabulary so every observed column has MANY nonzero rows per
    # entity — near-tied Pearson scores (e.g. single-observation columns)
    # break differently under float32 between the two paths, which is
    # tie-order ambiguity, not a correctness signal
    idx, vals, dense, uids, y, d = _sparse_re_data(n=512, d=64, n_users=8)
    # intercept column d-1 on every row
    idx[:, -1] = d - 1
    vals[:, -1] = 1.0
    dense[:, :] = 0.0
    np.add.at(dense, (np.repeat(np.arange(len(y)), idx.shape[1]),
                      idx.ravel()), vals.ravel())
    cfg = dict(random_effect_type="userId", feature_shard="u",
               solver=SolverConfig(max_iters=25), reg=Regularization(l2=1.0),
               projector=ProjectorType.INDEX_MAP,
               features_to_samples_ratio=0.25, intercept_index=d - 1)
    gd_s = GameData(y=y, features={"u": SparseShard(indices=idx, values=vals,
                                                    dim=d)},
                    id_tags={"userId": uids})
    gd_d = GameData(y=y, features={"u": dense}, id_tags={"userId": uids})
    cs = build_coordinate("u", gd_s, RandomEffectConfig(**cfg),
                          TaskType.LOGISTIC_REGRESSION)
    cd = build_coordinate("u", gd_d, RandomEffectConfig(**cfg),
                          TaskType.LOGISTIC_REGRESSION)
    # identical per-entity observed-column selections (sparse builds them
    # straight from COO rows; dense scans the densified block)
    for ps, pd in zip(cs._proj.projections, cd._proj.projections):
        sel_s = [set(r[r >= 0].tolist()) for r in ps.indices]
        sel_d = [set(r[r >= 0].tolist()) for r in pd.indices]
        assert sel_s == sel_d
        assert all(d - 1 in s for s, lanes in zip(sel_s, ps.indices)
                   if lanes[0] >= 0)  # intercept survives on real lanes
    off = np.zeros(len(y), np.float32)
    ms, _ = cs.update(off)
    md, _ = cd.update(off)
    np.testing.assert_allclose(ms.w_stack, md.w_stack, atol=5e-4)

    # factor-only normalization: sparse matches the densified INDEX_MAP path
    fac = np.linspace(0.5, 2.0, d).astype(np.float32)
    norm = NormalizationContext(factors=fac, shifts=None)
    cs_n = build_coordinate("u", gd_s, RandomEffectConfig(**cfg),
                            TaskType.LOGISTIC_REGRESSION, norm=norm)
    cd_n = build_coordinate("u", gd_d, RandomEffectConfig(**cfg),
                            TaskType.LOGISTIC_REGRESSION, norm=norm)
    ms_n, _ = cs_n.update(off)
    md_n, _ = cd_n.update(off)
    np.testing.assert_allclose(ms_n.w_stack, md_n.w_stack, atol=5e-4)


def test_sparse_re_unsupported_configs_raise():
    from photon_ml_tpu.game.config import RandomEffectConfig
    from photon_ml_tpu.types import ProjectorType, VarianceComputationType

    idx, vals, dense, uids, y, d = _sparse_re_data(n=256, d=256, n_users=8)
    shard = SparseShard(indices=idx, values=vals, dim=d)
    # RANDOM of a sparse shard needs projected_dim, like the dense path
    with pytest.raises(ValueError, match="projected_dim"):
        _re_coordinate(shard, uids, y, d, projector=ProjectorType.RANDOM)
    # BOTH variance kinds are exact under compaction and BUILD (the
    # full-space Hessian is block-diagonal; see _expand_compact_variances)
    for kind in (VarianceComputationType.SIMPLE, VarianceComputationType.FULL):
        c, _ = _re_coordinate(shard, uids, y, d, variance=kind)
        assert c._compact_variances


def test_sparse_re_random_projection_matches_densified():
    """RANDOM projection of a SPARSE shard (the round-3 refusal at the old
    game/coordinate.py:654): gathering the shared Gaussian matrix's rows
    through each lane's observed-column map computes exactly the densified
    x @ A (unobserved columns contribute zero either way), so with the same
    seed the two fits share one projected problem — coefficient AND score
    parity, while the sparse path never builds [E, S, d_full] tensors."""
    from photon_ml_tpu.types import ProjectorType

    idx, vals, dense, uids, y, d = _sparse_re_data(n=768, d=1024, n_users=16)
    cs, _ = _re_coordinate(SparseShard(indices=idx, values=vals, dim=d),
                           uids, y, d, projector=ProjectorType.RANDOM,
                           projected_dim=16)
    cd, _ = _re_coordinate(dense, uids, y, d,
                           projector=ProjectorType.RANDOM, projected_dim=16)
    off = np.zeros(len(y), np.float32)
    ms, _ = cs.update(off)
    md, _ = cd.update(off)
    assert ms.w_stack.shape == md.w_stack.shape == (16, d)
    # the projected designs are bitwise-different f32 reductions (dense x@A
    # vs compact gather-einsum), and warm solver iterations amplify the last
    # bits — same tolerance class as the other sparse/dense parity tests
    np.testing.assert_allclose(ms.w_stack, md.w_stack, atol=5e-3)
    np.testing.assert_allclose(cs.score(ms), cd.score(md), atol=1e-2)
    # the projected design blocks are small: d_proj(+intercept slot) wide,
    # nowhere near the 1024-wide densified blocks
    assert all(b.x.shape[2] <= 17 for b in cs._proj.buckets)


def test_sparse_re_box_constraints_match_densified():
    """Box constraints on a SPARSE random-effect shard: per-lane bounds
    gathered through each entity's observed-column map (the compact twin of
    the reference's full-space projectCoefficientsToSubspace,
    OptimizationUtils.scala) must reproduce the densified IDENTITY
    full-space constrained solve — including unobserved constrained
    features, which publish clip(0, lo, hi), on the host path AND through
    the fused program."""
    import jax.numpy as jnp

    idx, vals, dense, uids, y, d = _sparse_re_data(n=512, d=512, n_users=16)
    cons = ((0, -0.05, 0.05), (2, 0.01, 0.5), (5, -0.5, 0.5))
    cs, _ = _re_coordinate(SparseShard(indices=idx, values=vals, dim=d),
                           uids, y, d, constraints=cons)
    cd, _ = _re_coordinate(dense, uids, y, d, constraints=cons)
    off = np.zeros(len(y), np.float32)
    ms, _ = cs.update(off)
    md, _ = cd.update(off)
    for j, lo, hi in cons:
        assert np.all(ms.w_stack[:, j] >= lo - 1e-6)
        assert np.all(ms.w_stack[:, j] <= hi + 1e-6)
    np.testing.assert_allclose(ms.w_stack, md.w_stack, atol=1e-3)

    # an entity NOT observing constrained feature 2 (lo=0.01 > 0) publishes
    # the box projection of zero, exactly like the full-space solve
    hit = False
    for eid, (bi, lane) in cs.buckets.lane_of.items():
        obs = set(cs._proj.projections[bi].indices[lane].tolist()) - {-1}
        if 2 not in obs:
            np.testing.assert_allclose(ms.w_stack[ms.slot_of[eid], 2], 0.01,
                                       atol=1e-6)
            hit = True
    assert hit, "test data unexpectedly has every entity observing feature 2"

    # fused-path parity: one trace_update+publish == one host update
    state = cs.init_sweep_state()
    sdata = cs.sweep_data()
    state, _ = cs.trace_update(state, jnp.zeros(len(y), jnp.float32),
                               data=sdata)
    w_stack = np.asarray(cs.trace_publish(state, data=sdata))
    np.testing.assert_allclose(w_stack, ms.w_stack, atol=1e-5)


def test_sparse_re_box_with_factor_normalization_matches_densified():
    """Box constraints COMPOSED with factor-only normalization on a sparse
    shard: original-space bounds divide by each lane's gathered factor rows
    inside the vmapped solve (game/coordinate._one), which must agree with
    the densified IDENTITY path, where the full-space bounds divide by the
    full factor vector at build time (_box_from_constraints)."""
    from photon_ml_tpu.core.normalization import NormalizationContext
    import jax.numpy as jnp

    idx, vals, dense, uids, y, d = _sparse_re_data(n=512, d=256, n_users=16)
    cons = ((0, -0.05, 0.05), (3, 0.02, 0.4))
    fac = np.full(d, 0.5, np.float32)
    norm = NormalizationContext(factors=jnp.asarray(fac), shifts=None)
    cs_n, _ = _re_coordinate(SparseShard(indices=idx, values=vals, dim=d),
                             uids, y, d, constraints=cons, norm=norm)
    cd_n, _ = _re_coordinate(dense, uids, y, d, constraints=cons, norm=norm)
    off = np.zeros(len(y), np.float32)
    ms_n, _ = cs_n.update(off)
    md_n, _ = cd_n.update(off)
    for j, lo, hi in cons:
        assert np.all(ms_n.w_stack[:, j] >= lo - 1e-6)
        assert np.all(ms_n.w_stack[:, j] <= hi + 1e-6)
    # (no unnormalized comparison: L2 applies in TRANSFORMED space —
    # λ‖w/f‖² — so normalization legitimately moves the optimum; parity
    # target is the densified fit under the SAME context, as everywhere)
    np.testing.assert_allclose(ms_n.w_stack, md_n.w_stack, atol=1e-3)
    # with factor 0.5 the effective penalty is 4λ: bounded features must
    # still reach a binding bound somewhere or the box wasn't applied
    assert np.any(np.isclose(ms_n.w_stack[:, 0], -0.05, atol=1e-5) |
                  np.isclose(ms_n.w_stack[:, 0], 0.05, atol=1e-5))


@pytest.mark.parametrize("kind", ["SIMPLE", "FULL"])
def test_sparse_re_variances_exact_under_compaction(kind):
    """Variances under compaction are EXACT for both kinds: the full-space
    Hessian is block-diagonal (unobserved columns are identically zero for
    the entity), so observed features match the densified IDENTITY
    computation — SIMPLE from the compact diag, FULL from the compact
    Cholesky — and unobserved features carry the prior-only curvature
    1/λ2 — on the host path AND through the fused program."""
    from photon_ml_tpu.types import VarianceComputationType

    idx, vals, dense, uids, y, d = _sparse_re_data(n=512, d=256, n_users=16)
    l2 = 2.5
    def coord(features):
        from photon_ml_tpu.game.config import RandomEffectConfig
        from photon_ml_tpu.game.coordinate import build_coordinate
        from photon_ml_tpu.game.data import GameData
        from photon_ml_tpu.opt.types import SolverConfig

        cfg = RandomEffectConfig(random_effect_type="userId",
                                 feature_shard="u",
                                 solver=SolverConfig(max_iters=25),
                                 reg=Regularization(l2=l2),
                                 variance=VarianceComputationType[kind])
        gd = GameData(y=y, features={"u": features}, id_tags={"userId": uids})
        return build_coordinate("u", gd, cfg, TaskType.LOGISTIC_REGRESSION)

    cs = coord(SparseShard(indices=idx, values=vals, dim=d))
    cd = coord(dense)
    off = np.zeros(len(y), np.float32)
    ms, _ = cs.update(off)
    md, _ = cd.update(off)
    np.testing.assert_allclose(ms.w_stack, md.w_stack, atol=5e-4)
    assert ms.variances is not None and ms.variances.shape == md.variances.shape
    np.testing.assert_allclose(ms.variances, md.variances, rtol=2e-3)
    # unobserved features really are prior-only
    eid0 = sorted(cs.buckets.lane_of)[0]
    bi, lane = cs.buckets.lane_of[eid0]
    obs = set(cs._proj.projections[bi].indices[lane].tolist()) - {-1}
    unobs = [j for j in range(d) if j not in obs][:5]
    slot = ms.slot_of[eid0]
    np.testing.assert_allclose(ms.variances[slot][unobs], 1.0 / l2, rtol=1e-5)

    # fused program publishes the same variances
    import jax.numpy as jnp
    state = cs.init_sweep_state()
    sdata = cs.sweep_data()
    state, _ = cs.trace_update(state, jnp.zeros(len(y), jnp.float32),
                               data=sdata)
    v = cs.trace_variances(state, jnp.zeros(len(y), jnp.float32), data=sdata)
    v_stack = cs.export_variances(v)
    np.testing.assert_allclose(v_stack, ms.variances, rtol=2e-3)


def test_sparse_re_soa_newton_matches_vmapped(monkeypatch):
    """Narrow COMPACT sparse buckets ride the SoA Newton solver (the gate
    keys on SOLVE-space shapes, not the full vocabulary width) and must
    match the generic vmapped path bit-for-tolerance."""
    idx, vals, dense, uids, y, d = _sparse_re_data(
        seed=9, n=128, d=512, k=2, n_users=16)
    sh = SparseShard(indices=idx, values=vals, dim=d)
    cs, _ = _re_coordinate(sh, uids, y, d)
    assert cs._use_soa, (
        "narrow compact sparse buckets should gate onto SoA: shapes "
        + str([b.x.shape for b in cs._proj.buckets]))
    off = np.zeros(len(y), np.float32)
    ms, _ = cs.update(off)

    monkeypatch.setattr("photon_ml_tpu.opt.newton_soa.soa_eligible",
                        lambda dim, loss_name: False)
    cv, _ = _re_coordinate(sh, uids, y, d)
    assert not cv._use_soa
    mv, _ = cv.update(off)
    np.testing.assert_allclose(ms.w_stack, mv.w_stack, atol=5e-4)
    np.testing.assert_allclose(cs.score(ms), cv.score(mv), atol=5e-3)
