"""Compile each configuration's serving step for a described TPU v5e.

No chip is attached: the TPU compiler that ships with libtpu compiles the
engine's jitted paged step at the configuration's real widths and engine
settings, for a few token counts, and reports how long tracing and
compiling took and ``compiled.memory_analysis()``.  The step is the one
``ServeEngine`` jits (``repro.serve.step.make_paged_step``) with the
configuration's run settings, at full float32 matmul precision.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/rehearse.py \\
        [--config NAME ...] [--tokens 32 96 1024] [--slots N]

Prints one line per (configuration, token count) and, per
configuration, the bytes that stay on the chip beside the step: the
weights and the KV pool, which the step takes and returns whole.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
GB = 1e9


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", nargs="*",
                    default=[p.stem for p in sorted(
                        (HERE / "configs").glob("*.json"))])
    ap.add_argument("--tokens", type=int, nargs="*", default=[32, 1024])
    ap.add_argument("--slots", type=int, default=None,
                    help="override the configuration's slot count")
    ap.add_argument("--capacity", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(HERE.parent / "src"))

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import model
    from repro.kernels import ops
    from repro.models.lm import init_cache
    from repro.obs import NOOP
    from repro.sampling import SamplingConfig
    from repro.serve.step import make_paged_step

    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", "highest")
    ops.on_tpu = lambda: True          # compile the kernels, not interpret
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    for name in args.config:
        c = model.load(HERE / "configs" / f"{name}.json")
        eng = c["engine"]
        if args.layers:
            c["num_hidden_layers"] = args.layers
        slots = args.slots or eng["slots"]
        cap = args.capacity or eng["capacity"]
        cfg = model.model_config(c)
        bs = eng["kv_block_size"]
        nb = -(-cap // bs)
        params = on_chip(model.param_shapes(cfg))
        pools = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, slots * nb, bs, jnp.float32)))
        wbytes = sum(l.size * l.dtype.itemsize
                     for l in jax.tree.leaves(params))
        kbytes = sum(l.size * l.dtype.itemsize
                     for l in jax.tree.leaves(pools))
        print(f"{name}: {cfg.n_layers} layers, {slots} slots x {cap} "
              f"tokens; weights {wbytes / GB:.3f} GB, KV pool "
              f"{kbytes / GB:.3f} GB", flush=True)
        step = make_paged_step(cfg, model.run_config(c), NOOP,
                               SamplingConfig(method="greedy"))
        for T in args.tokens:
            i32 = jnp.int32

            def vec(*shape):
                return jax.ShapeDtypeStruct(shape, i32, sharding=chip)
            t0 = time.perf_counter()
            lowered = step.lower(params, pools, {"tokens": vec(T, 1)},
                                 vec(T), vec(T, nb), vec(T), vec(T), vec(T))
            t1 = time.perf_counter()
            try:
                compiled = lowered.compile()
            except Exception as e:          # what the chip would refuse
                print(f"  T={T}: refused: {str(e).splitlines()[0]}",
                      flush=True)
                continue
            t2 = time.perf_counter()
            m = compiled.memory_analysis()
            print(f"  T={T}: trace+lower {t1 - t0:.2f} s, compile "
                  f"{t2 - t1:.2f} s; arguments "
                  f"{m.argument_size_in_bytes / GB:.3f} GB, outputs "
                  f"{m.output_size_in_bytes / GB:.3f} GB, aliased "
                  f"{m.alias_size_in_bytes / GB:.3f} GB, temporaries "
                  f"{m.temp_size_in_bytes / GB:.3f} GB; peak beside "
                  f"nothing else "
                  f"{(m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes) / GB:.3f} GB; "
                  f"tpu_custom_call "
                  f"{compiled.as_text().count('tpu_custom_call')}",
                  flush=True)


if __name__ == "__main__":
    main()
