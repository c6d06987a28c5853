#!/usr/bin/env python3
"""Compile a training cell's fit program and report its memory.

    JAX_PLATFORMS=cpu python bench/tools/compile_check.py otto.train
    python bench/tools/compile_check.py --chip otto.train

The program is the fit's scan segment (`boosting.boost_scan`, one
``scan_chunk`` of rounds with the eval set) at the cell's sizes, compiled
for a described TPU v5e (no chip needed) or, with ``--chip``, for the
chip this process holds.  Prints what the compiler says the program
needs (temporaries, arguments, outputs) and how many Pallas kernels it
holds; it gives no time.  With ``--chip`` it then runs the program once
on zeros and prints the chip's memory statistics, to compare the
runtime's ``peak_bytes_in_use`` with the compiler's figures.
"""
import argparse
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def report(name, compiled):
    ma = compiled.memory_analysis()
    gib = 2.0 ** 30
    print(f"{name}: temp {ma.temp_size_in_bytes / gib:.2f} GiB, args "
          f"{ma.argument_size_in_bytes / gib:.2f} GiB, out "
          f"{ma.output_size_in_bytes / gib:.2f} GiB, alias "
          f"{ma.alias_size_in_bytes / gib:.2f} GiB, tpu_custom_call "
          f"{compiled.as_text().count('tpu_custom_call')}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chip", action="store_true")
    ap.add_argument("workloads", nargs="*", default=["otto.train"])
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from harness import common, train
    from repro.core import boosting as B
    if args.chip:
        common.device_info(1)
        common.enable_caches()
        dev = jax.devices()[0]
    else:
        from jax.experimental import topologies
        jax.config.update("jax_enable_compilation_cache", False)
        dev = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    one = SingleDeviceSharding(dev)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    for name in args.workloads:
        cell = common.load_cell(name)
        conf = cell["config"]
        n, nv, m, d = (conf["n_train"], conf["n_eval"], conf["n_features"],
                       conf["n_outputs"])
        cfg = train.gbdt_config(cell, 0, use_kernel="pallas",
                                hist_engine="subtract")
        if conf["task"] == "multiclass":
            ys = (S((n,), jnp.int32), S((nv,), jnp.int32))
        else:
            ys = (S((n, d), jnp.float32), S((nv, d), jnp.float32))
        specs = [S((n, d), jnp.float32), S((n, m), jnp.uint8), ys[0],
                 S((nv, d), jnp.float32), S((nv, m), jnp.uint8), ys[1]]
        key = jax.eval_shape(lambda: jax.random.key(0))
        key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one)
        compiled = B.boost_scan.lower(
            *specs, key, cfg=cfg, n_steps=min(cfg.scan_chunk, cfg.n_trees),
            has_eval=True).compile()
        report(f"{name} n={n} boost_scan", compiled)
        if args.chip:
            import json
            out = compiled(*[jnp.zeros(a.shape, a.dtype, device=dev)
                             for a in specs], jax.random.key(0))
            jax.block_until_ready(out)
            del out
            print(f"{name} memory_stats after one run: "
                  f"{json.dumps(dev.memory_stats())}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
