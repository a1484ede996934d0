"""Compile a configuration's serving programs for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 -m bench.aot --config qwen3-0.6b

No chip is needed: the TPU compiler is given one chip of a described
``v5e:2x2`` and the shapes of the program's arguments. It compiles the
quantization surgery and the mixed step at both widths (1 and
``prefill_chunk``) exactly as a run of the cell would, with the Pallas paths
forced (on the CPU they would resolve to the XLA twins), and prints each
program's ``memory_analysis()``. That is what the KV pool is sized from.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _pallas(policy: str) -> str:
    return ",".join(r + ":pallas" if "prequant" in r else r for r in policy.split(","))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.run import config_file, model_config, run_config
    from repro.kernels.flash_paged import set_paged_impl
    from repro.models import abstract_params, init_caches
    from repro.quant import apply_surgery
    from repro.serve.scheduler import build_mixed_step

    jax.config.update("jax_enable_compilation_cache", False)
    config = config_file(args.config)
    sv = config["serving"]
    pages = sv["num_pages"]
    cfg = model_config(config)
    rc = run_config(config, _pallas(config["quant_policy"]))
    set_paged_impl("pallas")
    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)

    def report(name, compiled):
        m = compiled.memory_analysis()
        row = {k: getattr(m, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")}
        print(json.dumps({"config": args.config, "program": name, **row}))

    raw = on_chip(abstract_params(cfg, rc))
    surg = jax.jit(lambda p: apply_surgery(cfg, rc, p)).lower(raw).compile()
    report("surgery", surg)
    params = on_chip(jax.eval_shape(lambda p: apply_surgery(cfg, rc, p), raw))
    caches = on_chip(jax.eval_shape(
        lambda: init_caches(cfg, rc, sv["max_batch"], sv["capacity"], num_pages=pages)))
    B = sv["max_batch"]
    mb = -(-sv["capacity"] // config["kv"]["block_size"])
    step = jax.jit(build_mixed_step(cfg, rc), donate_argnums=(1,))
    i32 = jnp.int32
    for width in (1, sv["prefill_chunk"]):
        args_ = (params, caches,
                 jax.ShapeDtypeStruct((B, width), i32, sharding=chip),
                 jax.ShapeDtypeStruct((B,), i32, sharding=chip),
                 jax.ShapeDtypeStruct((B,), i32, sharding=chip),
                 jax.ShapeDtypeStruct((B, mb), i32, sharding=chip))
        report(f"step_width_{width}", step.lower(*args_).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
