#!/usr/bin/env python3
"""Compile a cell's train step for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 chipbench/compile_check.py --workload <cell> \
        [--layers N ...] [--global-batch B]

Builds the step as ``harness.build`` does, but on the devices of a
described ``v5e:2x2`` topology (one chip, or the 2x2 mesh for a four-chip
cell), and prints for each depth the compiled program's
``memory_analysis`` (arguments, outputs, temporaries, generated code) and
its count of Pallas kernels (``tpu_custom_call``).  The depth and batch of
each configuration were chosen with it: the deepest whole count whose
arguments plus temporaries stay under 15.75 GB.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")]

LIMIT = 15.75e9


def compile_for(cell, layers: int | None, batch: int | None):
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from repro.configs.base import ShapeConfig, get_arch
    from repro.launch.steps import make_train_step
    from repro.launch.train import build_args, make_run
    from chipbench.spec import trainer_argv

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devs = np.array(topo.devices[:cell.chips]).reshape(cell.dp, cell.tp)
    mesh = Mesh(devs, ("data", "model"))
    over = dict(cell.config.get("overrides", {}))
    if layers:
        over["n_layers"] = layers
    cfg = dataclasses.replace(get_arch(cell.config["arch"]), **over)
    if batch:
        cell = dataclasses.replace(cell, traffic={**cell.traffic,
                                                  "global_batch": batch})
    run = make_run(build_args(trainer_argv(cell)))
    shape = ShapeConfig("bench", cell.seq_len, cell.global_batch, "train")
    bundle = make_train_step(cfg, run, mesh, shape)
    t = time.time()
    exe = bundle.fn.lower(*bundle.input_shapes).compile()
    return exe, time.time() - t, cfg


def compile_reference(cell, layers: int | None):
    """The reference step of the cell, compiled for the same devices."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from chipbench import model, reference

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    conf = dict(cell.config)
    if layers:
        conf["num_hidden_layers"] = layers
    dims = model.Dims.from_config(conf)
    s = reference.Setting(dims=dims,
                          wire=reference.Wire.from_traffic(cell.traffic),
                          ranks=cell.dp, micro=1,
                          opt=cell.traffic["optimizer"])
    pr = reference.build(s, topo.devices[:cell.chips])
    sds = lambda shp, dt, sh: jax.ShapeDtypeStruct(shp, dt, sharding=sh)
    shapes = dims.shapes()
    params = jax.tree.map(
        lambda shp, sh: sds(shp, jnp.float32, sh), shapes, pr.p_shard,
        is_leaf=lambda x: isinstance(x, tuple))
    errs = {n: sds(shp, jnp.float8_e4m3fn, pr.e_shard[n])
            for n, shp in pr.err_shapes.items()}
    rows = sds((cell.global_batch, cell.seq_len + 1), jnp.int32, pr.rep)
    t = sds((), jnp.float32, pr.rep)
    return pr.step.lower(params, rows, errs, params, params, t).compile()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, nargs="*", default=[None])
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--reference", action="store_true",
                    help="also compile the plain reference's step")
    args = ap.parse_args(argv)
    from chipbench.spec import load_cell

    cell = load_cell(args.workload)
    for layers in args.layers:
        exe, secs, cfg = compile_for(cell, layers, args.global_batch)
        ma = exe.memory_analysis()
        fit = ma.argument_size_in_bytes + ma.temp_size_in_bytes
        kernels = exe.as_text().count('custom_call_target="tpu_custom_call"')
        print(f"{cell.name} layers={cfg.n_layers} "
              f"batch={args.global_batch or cell.global_batch}: "
              f"arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, "
              f"outputs {ma.output_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {ma.temp_size_in_bytes / 1e9:.3f} GB, "
              f"code {ma.generated_code_size_in_bytes / 1e6:.1f} MB, "
              f"arguments+temporaries {fit / 1e9:.3f} GB "
              f"({'fits' if fit <= LIMIT else 'does not fit'} 15.75), "
              f"tpu_custom_call {kernels}, compiled in {secs:.1f} s",
              flush=True)
        if args.reference:
            ma = compile_reference(cell, layers).memory_analysis()
            print(f"  reference step: arguments "
                  f"{ma.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
                  f"{ma.temp_size_in_bytes / 1e9:.3f} GB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
