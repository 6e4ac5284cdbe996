"""Compile a cell's step programs at full size for a DESCRIBED v5e:2x2
(no chip attached) and print the compiler's `memory_analysis()`, which
decides batch sizes and pool sizes before any chip time is spent
(`/opt/skills/guides/on-chip-measurement`, section 2).

    JAX_PLATFORMS=cpu python -m benchmark.tools.size_cells --workload xl-train-fsdp4 --try global_batch=16,24
    JAX_PLATFORMS=cpu python -m benchmark.tools.size_cells --workload small-serve-chat --try num_pages=2048,2560

Nothing runs and nothing here is a measurement. The process's own
backend is the CPU, so the program's `jax.default_backend()` branches
are steered here (flash attention forced on, the paged kernel asked for
by name), never through an option of the program.
"""
from __future__ import annotations

import argparse
import json
import os
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

GIB = float(1 << 30)


def _take_the_tpu_branch() -> None:
    """`ops/flash_attention.py` asks the process's backend (the CPU
    here) whether to call its Pallas kernels; answer for the chip the
    program is being compiled for (`tests/test_tpu_compile.py`'s way).
    `determined_tpu.ops.flash_attention` names the function, so the
    module comes through importlib."""
    import importlib

    fa = importlib.import_module("determined_tpu.ops.flash_attention")
    fa._use_pallas = lambda: True


def sizes(lowered) -> dict:
    try:
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001 - the refusal IS the answer
        return {"refused": str(e).strip().splitlines()[0][-220:]}
    m = compiled.memory_analysis()
    got = {k: getattr(m, k + "_size_in_bytes") / GIB for k in
           ("argument", "output", "alias", "temp", "generated_code")}
    got["total"] = (got["argument"] + got["output"] - got["alias"]
                    + got["temp"] + got["generated_code"])
    got = {k: round(v, 3) for k, v in got.items()}
    text = compiled.as_text()
    got["collectives"] = {
        op: text.count(f" {op}(") + text.count(f" {op}-start(")
        for op in ("all-reduce", "all-gather", "reduce-scatter")}
    got["tpu_custom_calls"] = text.count("tpu_custom_call")
    # XLA under memory pressure "compresses" big buffers into another
    # layout and back: whole-buffer copies on every call. A pool at
    # which these appear in the decode step is too large to serve from.
    got["remat_copies"] = text.count("remat_compressed = ")
    return got


def train(cell, topo, global_batch: int) -> dict:
    import optax

    from benchmark.model import gpt_config_kwargs
    from determined_tpu import core
    from determined_tpu.exec.builtin_trials import SyntheticTrial
    from determined_tpu.parallel.mesh import MeshConfig, batch_axes, make_mesh
    from determined_tpu.trainer import Trainer

    _take_the_tpu_branch()
    t = cell.traffic
    kw = dict(gpt_config_kwargs(cell.config), attn_impl="flash")
    trial = SyntheticTrial({
        "model": "gpt2-small", "model_kw": kw, "seq_len": t["seq_len"],
        "batch_size": global_batch, "lr": t["lr"]})
    mesh = make_mesh(MeshConfig(**t["mesh"]),
                     devices=topo.devices[:cell.chips])
    trainer = Trainer(trial, core._context._dummy_init(), mesh=mesh)
    shardings = trainer._param_shardings()
    params = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(trainer._tx.init, params)
    opt_sh = optax.tree_utils.tree_map_params(
        trainer._tx, lambda _x, s: s, opt, shardings,
        transform_non_params=lambda _x: NamedSharding(mesh, P()))

    def with_sharding(tree, sh):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sh)

    rep = NamedSharding(mesh, P())
    state = {
        "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        "params": with_sharding(params, shardings),
        "opt_state": with_sharding(opt, opt_sh),
    }
    batch = {"tokens": jax.ShapeDtypeStruct(
        (global_batch, t["seq_len"]), jnp.int32,
        sharding=NamedSharding(mesh, P(batch_axes())))}
    with mesh:
        return sizes(trainer._build_step_fn().lower(
            state, batch, np.float32(1.0),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)))


def serve(cell, topo, overrides: dict) -> dict:
    from benchmark.model import gpt_config_kwargs
    from determined_tpu.models.gpt import GPT, GPTConfig
    from determined_tpu.ops.paged_attention import default_paged_block_h
    from determined_tpu.serving.config import ServingConfig
    from determined_tpu.serving.engine import GenerationEngine, _scatter_kv

    _take_the_tpu_branch()
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = ServingConfig.from_dict({**cell.traffic["serving"], **overrides})
    model = GPT(GPTConfig(**gpt_config_kwargs(cell.config)))
    c = model.config

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree.map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = s((c.n_layers, cfg.num_pages, cfg.page_size, c.n_heads,
              c.head_dim), c.dtype)
    b, per = cfg.max_batch_size, cfg.max_pages_per_request
    grid = s((cfg.prefill_rows, cfg.prefill_seq), jnp.int32)
    kv = s((c.n_layers, cfg.prefill_rows, cfg.prefill_seq, c.n_heads,
            c.head_dim), c.dtype)
    pages_max = (cfg.prefill_rows
                 * -(-cfg.prefill_seq // cfg.page_size) + b)
    out = {}
    out["prefill"] = sizes(jax.jit(model.prefill_kv).lower(
        params, grid, grid, grid))
    out["scatter"] = sizes(jax.jit(_scatter_kv, donate_argnums=(0, 1)).lower(
        pool, pool, kv, kv, s((pages_max, cfg.page_size), jnp.int32),
        s((pages_max,), jnp.int32)))
    me = types.SimpleNamespace(model=model)
    block_h = default_paged_block_h(c.n_heads, c.head_dim, cfg.page_size,
                                    c.dtype)

    def decode(params, last, lengths, active, ck, cv, pt, temps, key):
        return GenerationEngine._decode_step(
            me, params, last, lengths, active, ck, cv, pt, temps, key,
            q_pad=8, kernel="paged", block_h=block_h, interpret=False)

    out["decode"] = sizes(jax.jit(decode, donate_argnums=(4, 5)).lower(
        params, s((b,), jnp.int32), s((b,), jnp.int32), s((b,), bool),
        pool, pool, s((b, per), jnp.int32), s((b,), jnp.float32),
        s((2,), jnp.uint32)))
    params_gib = sum(
        np.prod(x.shape) * x.dtype.itemsize
        for x in jax.tree.leaves(params)) / GIB
    pool_gib = 2 * np.prod(pool.shape) * 2 / GIB
    worst = max(v["temp"] + v["output"] - v["alias"] + v["generated_code"]
                for v in out.values() if "refused" not in v)
    out["resident"] = {
        "params": round(params_gib, 3), "pool": round(pool_gib, 3),
        "worst_program_extra": round(worst, 3),
        "total": round(params_gib + pool_gib + worst, 3), "block_h": block_h}
    return out


def main() -> None:
    from jax.experimental import topologies

    from benchmark.run import Cell

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--try", dest="tries", default="",
                        help="key=v1,v2,...: one key of the traffic file")
    args = parser.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cell = Cell(args.workload)
    key, _, values = args.tries.partition("=")
    for value in (values.split(",") if values else [None]):
        if cell.traffic["kind"] == "train":
            n = int(value) if value else int(cell.traffic["global_batch"])
            got = train(cell, topo, n)
            label = f"global_batch={n}"
        else:
            over = {key: int(value)} if value else {}
            got = serve(cell, topo, over)
            label = f"{key}={value}" if value else "as the traffic file says"
        print(args.workload, label, json.dumps(got), flush=True)


if __name__ == "__main__":
    main()
