"""Rehearsal 3 of the on-chip-measurement guide, for a FOUR-chip training
cell: compile the cell's one sweep program for a DESCRIBED v5e 2x2 host (no
chip attached), print ``memory_analysis()`` (bytes on EACH device) and the
collectives the compiler put in, by name, shape and scope.

    JAX_PLATFORMS=cpu python benchmarks/tools/compile_for_v5e_x4.py \
        --config glmix_ml25m --set source_users=10000 --set users=10000 \
        --set source_items=4000 --set source_rows=1500001

The coordinates are built here under a mesh of four VIRTUAL CPU devices, at
the size given (the host holds the design: keep it small here; the real
size belongs on the chip), then every mesh the program closes over is
swapped for the described chips' and the program is lowered from the SHAPES
and shardings of its arguments.  ``has_tpu`` is patched to True in this
script only, so that the Mosaic kernels are in the program as on the chip.
Nothing runs: this says whether the TPU compiler takes the program (kernels
under ``shard_map``, the exchanges), what it names its collectives and what
a device must hold, never how fast it is.  A compile that passes is not a
chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

CHIPS = 4
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COLLECTIVE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*?) (all-reduce|all-gather|all-to-all|"
    r"reduce-scatter|collective-permute)(-start)?\(", re.M)


def compile_described(sweep, there):
    """``sweep``'s main program, its coordinates built under a mesh of
    devices that are HERE, compiled for the mesh ``there`` of the same
    shape (described chips): every mesh the program closes over is swapped
    (the coordinates' and, through ``_bind_solver``, their objectives' and
    solvers'), and the program is lowered from the shapes and partition
    specs of its arguments."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sweep._mesh = there
    for c in sweep.coordinates.values():
        c.mesh = there
        c._bind_solver()

    def described(a):
        if not hasattr(a, "shape"):
            return a
        spec = (a.sharding.spec if isinstance(a.sharding, NamedSharding)
                else P())
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(there, spec))

    call, _ = sweep._program_args(None, None, 0, None)
    return sweep._program.lower(*jax.tree.map(described, call)).compile()


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={CHIPS}")
    sys.path[:0] = [HERE, os.path.dirname(HERE)]
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="key=int override of a top-level size")
    ap.add_argument("--dry-run-sizes", action="store_true",
                    help="the configuration's dry_run sizes")
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    import run as harness

    catalog = harness.Catalog()
    cfg = harness.sized(catalog.json("configs", args.config),
                        args.dry_run_sizes)
    for kv in args.set:
        k, v = kv.split("=")
        cfg[k] = int(v)
    recipe = catalog.module("recipes", cfg["recipe"])
    train_fits = catalog.module("traffic", "train_fits")

    import photon_ml_tpu.ops.fused_glm as fused_glm
    import photon_ml_tpu.ops.soa_newton as soa_newton
    from photon_ml_tpu.game.fused import FusedSweep
    from photon_ml_tpu.parallel.mesh import make_mesh

    here = make_mesh(devices=jax.devices()[:CHIPS])
    data = recipe.make_training(cfg, 0, here)
    print("data made", flush=True)
    fused_glm.has_tpu = soa_newton.has_tpu = lambda: True
    coords = train_fits.build_coordinates(cfg, data, here)
    sweep = FusedSweep(coords, num_iterations=int(cfg["sweeps"]))
    print("coordinates built", flush=True)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    there = Mesh(np.asarray(topo.devices[:CHIPS]).reshape(here.devices.shape),
                 here.axis_names)
    compiled = compile_described(sweep, there)
    m, text = compiled.memory_analysis(), compiled.as_text()
    found = {}
    for name, shape, kind, start in COLLECTIVE.findall(text):
        line = next(l for l in text.splitlines() if f"%{name} = " in l
                    or f" {name} = " in l)
        scope = re.search(r'op_name="([^"]*)"', line)
        key = (kind + start, shape[:70],
               "/".join(p for p in (scope.group(1) if scope else "").split("/")
                        if p.startswith("photon.")))
        found.setdefault(key, []).append(name)
    out = {
        "config": args.config, "rows": len(data["y"]),
        "argument_size_in_bytes": int(m.argument_size_in_bytes),
        "output_size_in_bytes": int(m.output_size_in_bytes),
        "temp_size_in_bytes": int(m.temp_size_in_bytes),
        "alias_size_in_bytes": int(m.alias_size_in_bytes),
        "kernels_in_program": sorted(
            k for k in ("fused_glm_value_grad", "fused_glm_tail_value_grad",
                        "fused_glm_hvp", "soa_newton_step") if k in text),
        "collectives": [[*k, len(v), v[:3]] for k, v in sorted(found.items())],
    }
    out["total_bytes_a_device"] = (
        out["argument_size_in_bytes"] + out["output_size_in_bytes"]
        + out["temp_size_in_bytes"] - out["alias_size_in_bytes"])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
