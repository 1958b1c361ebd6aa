"""Compile rehearsal (on-chip-measurement guide, section 2): the two
training cells' descent programs, at their dry-run sizes, compiled for a
DESCRIBED v5e chip — no chip attached, nothing runs — and the op-to-layer
table read off the executable's text.  What the CPU tests cannot show: the
TPU compiler's own instruction names (the ones a device trace prints) land
under the right ``photon.*`` scope, the Mosaic kernels and the gather
fusions among them.

The topology is described inside a module-scoped fixture, never at import,
and every such compile of the repo's tests lives in this one file: only one
process may hold the TPU's library, and a worker that imports this file must
not load it.  ``has_tpu`` is patched here, in the test only, to steer the
program onto its TPU branch (as benchmarks/tools/compile_for_v5e.py does).
"""

from __future__ import annotations

import os
import sys

import jax
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path[:0] = [BENCH, REPO]

import run as harness  # noqa: E402

from photon_ml_tpu.game.fused import FusedSweep  # noqa: E402
from photon_ml_tpu.obs.trace import hlo_op_table  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_text(one_chip):
    """config -> the compiled HLO text of its descent program.  Around the
    compiles the persistent cache is off (an executable compiled for a
    described chip cannot be read back without one) and so is x64, which
    tests/conftest.py turns on: the chip runs float32, and Mosaic cannot
    lower the kernels' index arithmetic at 64 bits."""
    from jax.experimental.compilation_cache import compilation_cache

    import photon_ml_tpu.ops.fused_glm as fused_glm
    import photon_ml_tpu.ops.soa_newton as soa_newton

    catalog = harness.Catalog()
    train_fits = catalog.module("traffic", "train_fits")
    patch = pytest.MonkeyPatch()
    cache_was = jax.config.jax_enable_compilation_cache
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    texts = {}

    def text_of(config):
        if config not in texts:
            cfg = harness.sized(catalog.json("configs", config), True)
            recipe = catalog.module("recipes", cfg["recipe"])
            coords = train_fits.build_coordinates(
                cfg, recipe.make_training(cfg, 0), None)
            sweep = FusedSweep(coords, num_iterations=int(cfg["sweeps"]))
            args, _ = sweep._program_args(None, None, 0, None)
            shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip)
                if hasattr(a, "shape") else a, args)
            texts[config] = sweep._program.lower(*shapes).compile().as_text()
        return texts[config]

    patch.setattr(fused_glm, "has_tpu", lambda: True)
    patch.setattr(soa_newton, "has_tpu", lambda: True)
    try:
        yield text_of
    finally:
        patch.undo()
        jax.config.update("jax_enable_x64", x64_was)
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def layers_of(table, prefix):
    """{the photon.* scopes, joined} over the instructions named so."""
    return {"/".join(p for p in path.split("/") if p.startswith("photon."))
            for name, path in table.items() if name.startswith(prefix)}


def gather_fusions(text, table):
    """{scopes: result shape} of the gather fusions (``kind=kCustom``,
    metadata ``.../gather``) the table holds."""
    out = {}
    for line in text.splitlines():
        if "kind=kCustom" not in line or " fusion(" not in line:
            continue
        name = line.split(" = ", 1)[0].split("%")[-1]
        if table.get(name, "").endswith("/gather"):
            scopes = "/".join(p for p in table[name].split("/")
                              if p.startswith("photon."))
            out.setdefault(scopes, []).append(
                line.split(" = ", 1)[1].split("{", 1)[0])
    return out


def assert_solves_gather_nothing(gathers):
    """The L-BFGS history is newest-first (opt/lbfgs.py): every lane of a
    vmapped solve reads the slot of the recursion's own counter, so no
    gather fusion is left under a solve (a circular history cost nine a
    bucket), while the offsets gathers and the rescore keep theirs."""
    assert not [s for s in gathers if "photon.entity_solve" in s], gathers
    assert {s.split("/")[-1] for s in gathers} >= {"photon.entity_gather",
                                                   "photon.rescore"}


def test_glmix_chip_kernels_and_gathers_fall_under_their_layers(compiled_text):
    text = compiled_text("glmix_chip")
    table = hlo_op_table(text)
    assert layers_of(table, "fused_glm_value_grad") == {
        "photon.update.fixed/photon.fixed_solve"}
    assert layers_of(table, "soa_newton_step") == {
        "photon.update.per_user/photon.entity_solve.b0"}
    # 128 users' windows (32 active rows kept of 48: ISSUE 35) gathered out
    # of the 6,144 offsets as two rows of 128 each, no index a slot; 6,144
    # rows of w_stack gathered by slot (the row layout, at this size)
    assert gather_fusions(text, table) == {
        "photon.update.per_user/photon.entity_gather": ["f32[256,128]"],
        "photon.update.per_user/photon.rescore": ["f32[6144,4]"]}


def test_glmix3_wide_solver_loops_fall_under_their_buckets(compiled_text):
    text = compiled_text("glmix3_wide")
    table = hlo_op_table(text)
    assert "soa_newton_step" not in text
    assert layers_of(table, "fused_glm_value_grad") == {
        "photon.update.fixed/photon.fixed_solve"}
    loops = {(scopes, path.count("while/body"))
             for name, path in table.items() if name.startswith("while")
             for scopes in ["/".join(p for p in path.split("/")
                                     if p.startswith("photon."))]
             if "entity_solve" in scopes}
    for bucket in ("per_user/photon.entity_solve.b0",
                   "per_item/photon.entity_solve.b0",
                   "per_item/photon.entity_solve.b1"):
        # the scan's body, then: the solver's loop, the line search in it
        assert {("photon.update." + bucket, 1),
                ("photon.update." + bucket, 2)} <= loops
    assert_solves_gather_nothing(gather_fusions(text, table))


def test_glmix_ml20m_ragged_solves_gather_nothing(compiled_text):
    text = compiled_text("glmix_ml20m")
    table = hlo_op_table(text)
    solves = {scopes for scopes in layers_of(table, "while")
              if "photon.entity_solve" in scopes}
    # the dry-run recipe's counts fall into seven capacity classes a side
    assert len(solves) >= 10, solves
    assert_solves_gather_nothing(gather_fusions(text, table))


@pytest.mark.parametrize("config", ["glmix_chip", "glmix3_wide"])
def test_nearly_every_instruction_carries_a_scope(compiled_text, config):
    table = hlo_op_table(compiled_text(config))
    heavy = [n for n in table
             if n.split(".")[0].endswith(("fusion", "while", "custom-call"))
             or n.startswith(("fused_glm", "soa_newton"))]
    bare = [n for n in heavy if "photon." not in table[n]]
    assert len(heavy) > 50 and len(bare) <= 0.05 * len(heavy), bare


def test_fixed_effect_kernels_take_an_undivisible_design_in_place(one_chip):
    """``glmix_ml20m``'s fixed design, 13,017,636 rows x 128 float32 (6.7
    GB), is 6,356 blocks of 2,048 rows and 548 rows more.  Compiled for the
    chip, one evaluation runs the kernel twice (the whole blocks where they
    lie, the last rows as one small batch) and needs no temporary the size
    of the design: padding it would."""
    from jax.experimental.compilation_cache import compilation_cache

    import jax.numpy as jnp

    import photon_ml_tpu.ops.fused_glm as fused_glm
    from photon_ml_tpu.core.batch import DenseBatch
    from photon_ml_tpu.core.losses import loss_for_task
    from photon_ml_tpu.types import TaskType

    n, d = 13017636, 128
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)

    def value_grad(w, x, y, o, wt):
        return fused_glm.fused_value_and_grad(
            loss, w, DenseBatch(x=x, y=y, offset=o, weight=wt))

    def hvp(w, x, y, o, wt):
        return fused_glm.fused_hvp(
            loss, w, w, DenseBatch(x=x, y=y, offset=o, weight=wt))

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    patch = pytest.MonkeyPatch()
    patch.setattr(fused_glm, "has_tpu", lambda: True)
    cache_was = jax.config.jax_enable_compilation_cache
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    try:
        for f in (value_grad, hvp):
            compiled = jax.jit(f).lower(shape(d), shape(n, d), shape(n),
                                        shape(n), shape(n)).compile()
            text = compiled.as_text()
            assert text.count("tpu_custom_call") == 2
            # a pass over the design stays ONE call of the main kernel: the
            # last rows run under another name (fixed_passes_per_fit and
            # fused_glm_hbm_share count calls by name)
            assert text.count("fused_glm_tail_") >= 1
            design = n * d * 4
            assert compiled.memory_analysis().temp_size_in_bytes < design // 8
    finally:
        patch.undo()
        jax.config.update("jax_enable_x64", x64_was)
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def test_glmix_ml25m_exchanges_compile_for_four_chips(topo, compiled_text):
    """The sharded descent program (ISSUE 30) at ``glmix_ml25m``'s dry-run
    sizes, its random effects' designs entity-major as in the cell,
    compiled for the DESCRIBED 2x2 host: the coordinates are built under a
    mesh of four of conftest's virtual CPU devices, then every mesh the
    program closes over is swapped for the described chips'
    (benchmarks/tools/compile_for_v5e_x4.py does the same at any size).
    The TPU compiler takes the Mosaic kernels under ``shard_map``, and the
    exchanges stay what ``parallel/mesh.py`` wrote: the four all-gathers
    of an ``[n]`` vector (shards that end inside a tile came back as
    all-reduces of a zero-padded vector, with no scope: hence
    ``mesh.padded_samples``), each under its ``photon.exchange`` scope,
    and no collective moves a design."""
    import numpy as np
    from jax.sharding import Mesh

    sys.path.insert(0, os.path.join(BENCH, "tools"))
    from compile_for_v5e_x4 import compile_described

    from photon_ml_tpu.obs.trace import hlo_collectives
    from photon_ml_tpu.parallel import bucketing
    from photon_ml_tpu.parallel.mesh import make_mesh

    compiled_text  # its patches: has_tpu, x64 off, no persistent cache
    catalog = harness.Catalog()
    cfg = harness.sized(catalog.json("configs", "glmix_ml25m"), True)
    here = make_mesh(devices=jax.devices()[:4])
    there = Mesh(np.asarray(topo.devices[:4]).reshape(here.devices.shape),
                 here.axis_names)
    data = catalog.module("recipes", cfg["recipe"]).make_training(cfg, 0, here)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bucketing, "NARROW_SCORE_PAD_BYTES_MIN", 1)
        coords = catalog.module("traffic", "train_fits").build_coordinates(
            cfg, data, here)
    assert all(c._em is not None and c._em.pos is not None
               for c in list(coords.values())[1:])
    sweep = FusedSweep(coords, num_iterations=int(cfg["sweeps"]))
    args, _ = sweep._program_args(None, None, 0, None)
    compiled = compile_described(sweep, there)
    text = compiled.as_text()
    table, named = hlo_op_table(text), hlo_collectives(text)
    assert layers_of(table, "fused_glm_value_grad") == {
        "photon.update.fixed/photon.fixed_solve"}
    gathers = sorted("/".join(p for p in table[n].split("/")
                              if p.startswith("photon."))
                     for n, kind in named.items()
                     if kind.startswith("all-gather"))
    assert gathers == [
        "photon.update.per_item/photon.exchange.offsets",
        "photon.update.per_item/photon.rescore/photon.exchange.scores",
        "photon.update.per_user/photon.exchange.offsets",
        "photon.update.per_user/photon.rescore/photon.exchange.scores"]
    # every collective the program has sits under an exchange scope, but
    # the fixed design's global padding at this size (section 3 of the
    # issue: a small design is padded to a block a device, and the [n]
    # vectors are shifted to its shards by collective-permutes)
    for name, kind in named.items():
        assert "photon.exchange." in table[name] or (
            kind.startswith("collective-permute")
            and "photon.update.fixed" in table[name]), (name, table[name])
    # the way back to sample order inside the ``scores`` exchange (ISSUE
    # 33): per-user rows arrive grouped by user and are un-padded out of a
    # dynamic slice of the whole vector, no gather; a movie's rows lie
    # anywhere and keep one gathered index a sample
    assert [c._em.back for c in list(coords.values())[1:]] == ["unpad",
                                                               "gather"]
    for cid, gathered in (("per_user", False), ("per_item", True)):
        scoped = [line for line in text.splitlines()
                  if f"photon.update.{cid}/" in line
                  and "photon.exchange.scores" in line]
        assert any(" gather(" in line for line in scoped) == gathered, cid
        assert any("dynamic-slice(" in line for line in scoped) != gathered
    n_pad = sweep._base.shape[0]
    assert n_pad % (4 * 1024) == 0 and n_pad - 9217 < 4 * 1024
    # what a device must hold is a quarter of the arguments, not all
    held = sum(a.nbytes for a in jax.tree.leaves(args) if hasattr(a, "nbytes"))
    assert compiled.memory_analysis().argument_size_in_bytes < 0.5 * held
