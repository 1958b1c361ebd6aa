"""Rehearsal 3 of the on-chip-measurement guide, for a training cell:
compile the cell's ONE sweep program at its real size for a DESCRIBED v5e
chip (no chip attached) and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python benchmarks/tools/compile_for_v5e.py \
        --config glmix3_wide [--set users=32768 --set items=16384]

The coordinates are built here on the CPU at full size (the host needs the
design's bytes in RAM), then the program is lowered for the described
device from the SHAPES of its arguments.  ``has_tpu`` is patched to True in
this script only, so that the Mosaic kernels are in the program as on the
chip.  Nothing runs: this says whether the program fits and what it needs,
never how fast it is.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="key=int override of a top-level size")
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run as harness

    catalog = harness.Catalog()
    cfg = harness.sized(catalog.json("configs", args.config), False)
    for kv in args.set:
        k, v = kv.split("=")
        cfg[k] = int(v)
    recipe = catalog.module("recipes", cfg["recipe"])
    train_fits = catalog.module("traffic", "train_fits")

    import photon_ml_tpu.ops.fused_glm as fused_glm
    import photon_ml_tpu.ops.soa_newton as soa_newton
    from photon_ml_tpu.game.fused import FusedSweep

    data = recipe.make_training(cfg, 0)
    print("data made", flush=True)
    fused_glm.has_tpu = soa_newton.has_tpu = lambda: True
    coords = train_fits.build_coordinates(cfg, data, None)
    sweep = FusedSweep(coords, num_iterations=int(cfg["sweeps"]))
    print("coordinates built", flush=True)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    regs = tuple(sweep.coordinates[cid].config.reg for cid in sweep.order)
    call = (*sweep.init_carry(None), sweep._vars0, regs,
            jax.random.PRNGKey(0), sweep._base, sweep._datas)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
        if hasattr(a, "shape") else a, call)
    arg_bytes = sum(a.size * a.dtype.itemsize
                    for a in jax.tree.leaves(call) if hasattr(a, "size"))
    compiled = sweep._program.lower(*shapes).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    out = {
        "config": args.config,
        "sizes": {k: cfg[k] for k in ("users", "items", "rows_per_user")
                  if k in cfg},
        "logical_argument_bytes": int(arg_bytes),
        "argument_size_in_bytes": int(m.argument_size_in_bytes),
        "output_size_in_bytes": int(m.output_size_in_bytes),
        "temp_size_in_bytes": int(m.temp_size_in_bytes),
        "alias_size_in_bytes": int(m.alias_size_in_bytes),
        "generated_code_size_in_bytes": int(m.generated_code_size_in_bytes),
        "kernels_in_program": sorted(
            k for k in ("fused_glm_value_grad", "fused_glm_hvp",
                        "soa_newton_step") if k in text),
        "bucket_shapes": {
            cid: [list(b.x.shape) for b in c.buckets.buckets]
            for cid, c in coords.items() if hasattr(c, "buckets")},
    }
    out["total_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          + out["temp_size_in_bytes"]
                          - out["alias_size_in_bytes"])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
