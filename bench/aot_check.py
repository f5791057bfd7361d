"""Compile a cell's chunk step at the cell's sizes for a described TPU v5e,
without a chip, and print XLA's memory analysis.

    JAX_PLATFORMS=cpu python3 bench/aot_check.py --workload <cell> [--width 16]

The bound weights are made for one layer and their shapes stretched to the
configuration's depth, so nothing of full size is allocated on the host.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--widths", default="16,1")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    from bench.harness.spec import Benchmark
    from repro.kernels import ops
    from repro.launch import engine
    from repro.models import get_model

    bench = Benchmark()
    wl = bench.workload(args.workload)
    conf = bench.config_file(wl["config"])
    depth = conf["num_hidden_layers"]
    one = dict(conf, num_hidden_layers=1)
    built = bench.builder(conf).build(one, 0)
    cfg = dataclasses.replace(built.cfg, n_layers=depth)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def abstract(path, x):
        shape = tuple(x.shape)
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        if "layers" in keys and shape and shape[0] == 1:
            shape = (depth,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=dev)

    params = jax.tree_util.tree_map_with_path(abstract, built.params)
    eng_kw = built.engine_kwargs
    b, max_len = eng_kw["max_slots"], eng_kw["max_len"]
    bs = eng_kw["block_size"]
    model = get_model(cfg)
    n_blocks = b * -(-max_len // bs)
    cache = jax.eval_shape(lambda: model.init_paged_cache(b, max_len,
                                                          n_blocks, bs))
    state = {"positions": jnp.zeros(b, jnp.int32),
             "counters": jnp.zeros(b, jnp.int32),
             "last_tok": jnp.zeros((b, 1), jnp.int32),
             "active": jnp.zeros(b, bool),
             "temperature": jnp.zeros(b, jnp.float32),
             "top_k": jnp.zeros(b, jnp.int32),
             "top_p": jnp.ones(b, jnp.float32),
             "keys": jnp.zeros((b, 2), jnp.uint32),
             "eos": jnp.zeros(b, jnp.int32),
             "budget": jnp.zeros(b, jnp.int32)}
    put = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev)  # noqa: E731
    cache, state = jax.tree.map(put, cache), jax.tree.map(put, state)
    ops.on_tpu = lambda: True                 # take the Mosaic branches
    chunk, _, _ = engine._build_paged_steps(cfg, built.policy)
    for t in (int(w) for w in args.widths.split(",")):
        tokens = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=dev)
        vec = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=dev)
        emit = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=dev)
        compiled = chunk.lower(params, tokens, cache, state, vec,
                               emit).compile()
        print(f"{args.workload} chunk step width {t}: "
              f"{compiled.memory_analysis()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
