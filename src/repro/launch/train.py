"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On a real cluster every host runs this same script (jax.distributed
initializes from the TPU environment); on CPU it trains reduced configs for
the examples/tests. XLA latency-hiding-scheduler flags are set before jax
import so collective/compute overlap is on for real runs (harmless on CPU).
"""

import os

# collective/compute overlap (distributed-optimization trick #4, DESIGN §3):
# enable XLA's latency-hiding scheduler + async collectives before jax init.
_overlap_flags = (
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_megacore_fusion_allow_ags=true "
    "--xla_enable_async_collective_permute=true "
    "--xla_enable_async_all_gather=true"
)
if "dryrun" not in os.environ.get("REPRO_MODE", "") and os.environ.get(
    "REPRO_TPU", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _overlap_flags
    ).strip()

import argparse
import dataclasses

import jax

from ..configs.base import SHAPES, RunConfig, ShapeConfig, get_config
from ..data import make_batches
from ..parallel.sharding import use_mesh
from ..train import Trainer
from .compile_cache import enable_compile_cache
from .mesh import make_local_mesh, make_production_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None, help="assigned shape name (default: custom)")
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--gemm-backend", default="bf16", choices=["bf16", "int8", "int4", "int2"],
                    help="uniform precision (shorthand for --policy '*=<kind>')")
    ap.add_argument("--policy", default=None,
                    help="per-layer mixed-precision QuantPolicy, e.g. "
                         "'attn.*=int8,mlp.*=int2,*=bf16' (DESIGN.md §7)")
    ap.add_argument("--moments", default="float32", choices=["float32", "int8"])
    ap.add_argument("--grad-compression", default="none", choices=["none", "int8_ef"])
    ap.add_argument("--remat", default="block", choices=["none", "block", "full"])
    ap.add_argument("--dtype", default=None, help="compute dtype (default bf16; f32 on CPU)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--data", type=int, default=1, help="local mesh data-axis size")
    ap.add_argument("--model", type=int, default=1, help="local mesh model-axis size")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    on_cpu = jax.default_backend() == "cpu"
    dtype = args.dtype or ("float32" if on_cpu else "bfloat16")
    from ..quant.policy import QuantPolicy, load_policy

    policy = load_policy(args.policy) or QuantPolicy.parse(f"*={args.gemm_backend}")
    if policy.any_prequant:
        ap.error("prequant policies are serving-time (packed frozen weights); "
                 "train with dynamic rules, e.g. --policy '*=int8'")
    rc = RunConfig(
        dtype=dtype,
        param_dtype=dtype,
        quant_policy=policy,
        remat=args.remat,
        lr=args.lr,
        total_steps=args.steps,
        warmup_steps=max(1, args.steps // 10),
        moments_dtype=args.moments,
        grad_compression=args.grad_compression,
        microbatches=args.microbatches,
    )
    shape = (
        SHAPES[args.shape]
        if args.shape
        else ShapeConfig("custom", args.seq_len, args.global_batch, "train")
    )

    mesh = (
        make_production_mesh(multi_pod=args.multi_pod)
        if args.production_mesh
        else make_local_mesh(args.data, args.model)
    )
    print(f"[launch] {args.arch} on mesh {dict(mesh.shape)} | {shape}")

    with use_mesh(mesh, overrides=rc.sharding_overrides):
        trainer = Trainer(
            cfg, rc, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every, seed=args.seed
        )
        batches = make_batches(cfg, shape, seed=args.seed, start_step=trainer.step)
        try:
            trainer.run(batches, args.steps - trainer.step)
        finally:
            batches.close()
    print(f"[launch] done at step {trainer.step}; watchdog {trainer.clock.summary()}")
    return trainer


if __name__ == "__main__":
    main()
