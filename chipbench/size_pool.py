"""Size a configuration's KV pool from the compiled verify step.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 chipbench/size_pool.py \
        mistral-7b [pool_blocks ...]

Compiles the fused verify step (greedy epilogue, the widest feed bucket
of 256 rows per slot, every slot) for a described, unattached v5e and
prints its ``memory_analysis()``.  The count follows ``chip_smoke.py``'s
depth rule (arguments + temporaries + unaliased outputs) and adds the
pool once more, since the layer scan appears to copy it (PERF.md).  A
pool fits when that count stays within one chip's 16 GB.  Nothing
runs and no time is measured.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

HBM = 16e9
WIDEST = 256


def count(name: str, pool_blocks: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import cells, weights
    import repro.kernels.decode_gqa.decode_gqa as dg
    import repro.kernels.partial_prefill.partial_prefill as pp
    from repro.models import model as M
    from repro.models.steps import make_cloud_verify_step

    for mod in (dg, pp):            # compile the kernels, not the interpreter
        mod.resolve_interpret = lambda interpret: False
    model = cells.load_json(cells.HERE / "configs" / f"{name}.json")
    cfg = cells.model_config(model)
    sv = model["serving"]
    B = sv["slots"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    params = on_chip(weights.param_shapes(cfg))
    cache = on_chip(jax.eval_shape(lambda: M.init_cache(
        cfg, B, sv["s_max"], cache_impl="paged", block_size=sv["block_size"],
        pool_blocks=pool_blocks)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa
    step = jax.jit(make_cloud_verify_step(cfg, with_dists=False),
                   donate_argnums=1)
    ma = step.lower(params, cache, i32(B, WIDEST), i32(B, WIDEST),
                    i32(B, WIDEST), i32(B, 8), i32(B)).compile(
                    ).memory_analysis()
    leaves = jax.tree.leaves
    pool = sum(x.size * x.dtype.itemsize for x in leaves(cache))
    wts = sum(x.size * x.dtype.itemsize for x in leaves(params))
    need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    return dict(config=name, layers=cfg.n_layers, pool_blocks=pool_blocks,
                pool_tokens=pool_blocks * sv["block_size"],
                weight_bytes=wts, pool_bytes=pool,
                argument_bytes=ma.argument_size_in_bytes,
                temp_bytes=ma.temp_size_in_bytes,
                output_bytes=ma.output_size_in_bytes,
                alias_bytes=ma.alias_size_in_bytes, need_bytes=need,
                counted_bytes=need + pool, fits=need + pool <= HBM)


if __name__ == "__main__":
    import json

    import jax

    from chipbench import cells

    jax.config.update("jax_enable_compilation_cache", False)
    name = sys.argv[1]
    blocks = [int(a) for a in sys.argv[2:]] or [cells.load_json(
        cells.HERE / "configs" / f"{name}.json")["serving"]["pool_blocks"]]
    for nb in blocks:
        print(json.dumps(count(name, nb)), flush=True)
