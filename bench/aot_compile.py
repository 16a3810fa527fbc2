"""Compile a cell's programs for a described TPU v5e, with no chip attached.

    JAX_PLATFORMS=cpu python bench/aot_compile.py <cell> [<cell> ...]

For each cell: every prefill bucket its traffic can reach and its decode
step, at the configuration's full size, compiled by the TPU compiler for
one chip of a described v5e:2x2; prints each program's
``memory_analysis()`` (argument, output and temporary bytes) and the
resident state the engine keeps besides (pool, page store, weights). A
compile that passes here is not a chip run: it gives sizes, never times.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]


def main(cells):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import harness
    from repro.models import transformer_lm as TLM
    from repro.parallel.sharding import DEFAULT_RULES
    from repro.serve.engine import compiled_fns

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    gb = 1e-9
    for name in cells:
        cell = harness.load_cell(name)
        conf = cell.conf
        cfg = harness.arch_config(conf)
        gen = harness.load_module(BENCH / "gen" / f"{cell.mix['kind']}.py")
        traffic = gen.Traffic(cell.mix, 0, cfg.vocab, conf["slots"], 30.0)
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one),
            harness.arch_module(conf).shapes(conf),
            is_leaf=lambda x: isinstance(x, tuple))
        pool = jax.tree.map(spec, jax.eval_shape(
            lambda: TLM.init_cache(cfg, conf["slots"], conf["max_len"],
                                   jnp.bfloat16)))
        fresh = jax.tree.map(spec, jax.eval_shape(
            lambda: TLM.init_cache(cfg, 1, conf["max_len"], jnp.bfloat16)))
        pages = jax.eval_shape(lambda: TLM.init_page_store(
            cfg, conf["cache_pages"], conf["page_size"], jnp.bfloat16))
        nbytes = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                               for x in jax.tree.leaves(t))
        print(f"{name}: pool {nbytes(pool) * gb:.3f} GB, page store "
              f"{nbytes(pages) * gb:.3f} GB, weights "
              f"{nbytes(params) * gb:.3f} GB", flush=True)
        prefill, decode = compiled_fns(cfg, DEFAULT_RULES)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                               sharding=one)
        lo = 8 if traffic.shares_prefix else harness.pow2_at_least(
            traffic.min_prompt)
        hi = min(conf["max_len"], harness.pow2_at_least(traffic.max_prompt))
        progs = [(f"prefill {b}", prefill, (params, i32(1, b), fresh,
                                            i32(1), i32()))
                 for b in (8 << i for i in range(12)) if lo <= b <= hi]
        progs.append((f"decode {conf['slots']}", decode,
                      (params, pool, i32(conf["slots"], 1),
                       i32(conf["slots"]))))
        for label, fn, args in progs:
            m = fn.lower(*args).compile().memory_analysis()
            print(f"  {label}: arguments {m.argument_size_in_bytes * gb:.3f}"
                  f" GB, outputs {m.output_size_in_bytes * gb:.3f} GB, "
                  f"temporaries {m.temp_size_in_bytes * gb:.3f} GB",
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
