"""`size_cells.py`'s method for a `"kind": "train_lm"` cell: compile the
cell's train step at full size for a DESCRIBED v5e (no chip attached)
and print the compiler's `memory_analysis()`, which decides the batch.

    JAX_PLATFORMS=cpu python -m benchmark.tools.size_train_lm \\
        --workload qwen3next-train-8k-ep16share --try global_batch=1,2,3,4

Nothing runs and nothing here is a measurement (`size_cells.py` cannot
be edited by the PR that added this file, and builds GPT-2 only).
"""
from __future__ import annotations

import argparse
import json

from benchmark.tools import size_cells  # sets JAX_PLATFORMS, TPU_LOG_DIR

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def _take_the_tpu_branch() -> None:
    """`size_cells._take_the_tpu_branch`, and the same for the attention
    dispatcher, which asks the process's backend too when a model leaves
    the implementation to it (`impl="auto"`: flash on a TPU)."""
    import importlib

    size_cells._take_the_tpu_branch()
    attention = importlib.import_module("determined_tpu.models.attention")
    resolve = attention._resolve_impl

    def on_the_chip(impl, mesh, seq):
        got = resolve(impl, mesh, seq)
        flash = impl == "auto" and got == "dense" and seq % 128 == 0
        return "flash" if flash else got

    if resolve.__name__ != "on_the_chip":
        attention._resolve_impl = on_the_chip


def train(cell, topo, global_batch: int):
    """What `size_cells.sizes` makes of the cell's compiled train step
    (its `memory_analysis()`, or whatever a test puts in its place)."""
    import optax

    from benchmark.drivers import train_lm
    from determined_tpu import core
    from determined_tpu.exec.builtin_trials import SyntheticTrial
    from determined_tpu.parallel.mesh import MeshConfig, batch_axes, make_mesh
    from determined_tpu.trainer import Trainer

    _take_the_tpu_branch()
    t = cell.traffic
    hparams = train_lm.trial_hparams(cell.config, t, global_batch)
    mesh = make_mesh(MeshConfig(**t["mesh"]),
                     devices=topo.devices[:cell.chips])
    trainer = Trainer(SyntheticTrial(hparams), core._context._dummy_init(),
                      mesh=mesh)
    shardings = trainer._param_shardings()
    params = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(trainer._tx.init, params)
    rep = NamedSharding(mesh, P())
    opt_sh = optax.tree_utils.tree_map_params(
        trainer._tx, lambda _x, s: s, opt, shardings,
        transform_non_params=lambda _x: rep)

    def with_sharding(tree, sh):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sh)

    state = {
        "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        "params": with_sharding(params, shardings),
        "opt_state": with_sharding(opt, opt_sh),
    }
    batch = {"tokens": jax.ShapeDtypeStruct(
        (global_batch, t["seq_len"]), jnp.int32,
        sharding=NamedSharding(mesh, P(batch_axes())))}
    with mesh:
        return size_cells.sizes(trainer._build_step_fn().lower(
            state, batch, np.float32(1.0),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)))


def main() -> int:
    from jax.experimental import topologies

    from benchmark.run import Cell

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--try", dest="tries", default="global_batch=")
    args = parser.parse_args()
    cell = Cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    key, _, values = args.tries.partition("=")
    assert key == "global_batch", key
    for v in ([int(x) for x in values.split(",") if x]
              or [int(cell.traffic["global_batch"])]):
        print(json.dumps({"global_batch": v, **train(cell, topo, v)}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
