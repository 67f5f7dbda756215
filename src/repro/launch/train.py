"""Training launcher: data -> step -> checkpoint loop with fault tolerance.

Runs on whatever mesh fits the local device count (the production mesh on
real pods; a debug mesh under CPU).  Features exercised here and tested
in ``tests/test_fault.py``:

* deterministic, host-sharded data (any worker can regenerate any shard),
* step-granular async-ish checkpointing (writes happen off the step path),
* crash/restart resume (``--simulate-failure-at`` injects a crash),
* elastic restore onto a different mesh shape,
* optional int8+error-feedback gradient compression (``--compress``),
* optional FSDP weight sharding (``--fsdp`` — the multicast data path).

Usage (CPU example, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --reduced --steps 50 --batch 8 --seq 128 --mesh-data 1 --mesh-model 1
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import kernels, runtime
from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCHS, get_config
from repro.configs.shapes import ShapeCfg
from repro.data.pipeline import DataConfig, sharded_batch
from repro.dist import sharding as shd
from repro.dist.step import build_train_step
from repro.launch.mesh import make_debug_mesh
from repro.models import lm
from repro.nn.spec import abstract_params, init_params
from repro.optim import adamw


def train_loop(args) -> dict:
    if getattr(args, "kernel_policy", None):
        # benchmarks force schedules/backends here; REPRO_KERNEL_POLICY
        # works too, this flag just wins over the env var.  Training no
        # longer pins the reference backend: every pallas schedule has a
        # custom VJP, so the default policy trains through the fused
        # kernels on TPU (and the reference backend off-TPU, as always).
        kernels.set_policy(args.kernel_policy)
    cfg = get_config(args.arch, reduced=args.reduced)
    if cfg.family == "audio":
        raise SystemExit("use examples/train_lm.py-style drivers for enc-dec")
    mesh = make_debug_mesh(data=args.mesh_data, model=args.mesh_model)

    # shape override for CPU-scale runs
    import repro.configs.shapes as shapes_mod

    shape = ShapeCfg("custom", "train", args.seq, args.batch)
    shapes_mod.SHAPES["custom"] = shape

    opt_cfg = adamw.AdamWConfig(
        lr=args.lr, warmup_steps=max(args.steps // 20, 5), total_steps=args.steps
    )
    bundle = build_train_step(
        cfg, mesh, "custom",
        fsdp=args.fsdp, compress_pod_grads=args.compress,
        opt_cfg=opt_cfg, loss_chunk=None if args.seq <= 512 else 512,
    )
    step_fn = jax.jit(
        bundle.fn,
        in_shardings=bundle.in_shardings,
        out_shardings=bundle.out_shardings,
        donate_argnums=bundle.donate_argnums,
    )

    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
                          seed=args.seed)
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)

    spec_tree = lm.model_spec(cfg)
    p_sh = shd.param_shardings(cfg, spec_tree, mesh, fsdp=args.fsdp)

    start = 0
    with jax.set_mesh(mesh):
        latest = ckpt.latest_step()
        if latest is not None and args.resume:
            print(f"resuming from checkpoint step {latest}")
            template = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), abstract_params(spec_tree)
            )
            params = ckpt.restore(latest, template, shardings=p_sh)
            opt_state = adamw.init(params, opt_cfg)  # moments restart (demo scale)
            start = latest
        else:
            params = jax.tree.map(
                lambda x, s: jax.device_put(x, s),
                init_params(spec_tree, jax.random.PRNGKey(args.seed)),
                p_sh,
            )
            opt_state = adamw.init(params, opt_cfg)

        err_state = None
        if args.compress:
            from repro.dist.compression import init_error_state

            err_state = init_error_state(params)

        losses = []
        ba = shd.batch_axes(mesh, args.batch)
        t0 = time.time()
        for step in range(start, args.steps):
            if args.simulate_failure_at is not None and step == args.simulate_failure_at:
                raise RuntimeError(f"simulated node failure at step {step}")
            batch = sharded_batch(data_cfg, step, mesh, ba)
            sarg = jnp.int32(step)
            if args.compress:
                params, opt_state, err_state, loss, metrics = step_fn(
                    params, opt_state, err_state, batch, sarg
                )
            else:
                params, opt_state, loss, metrics = step_fn(params, opt_state, batch, sarg)
            losses.append(float(loss))
            if step % args.log_every == 0:
                print(f"step {step:5d} loss {float(loss):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({(time.time()-t0)/max(step-start+1,1):.2f}s/step)")
            if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, params, meta={
                    "arch": cfg.name, "mesh": dict(mesh.shape), "loss": float(loss),
                })
    return {"losses": losses, "final_loss": losses[-1] if losses else None}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--simulate-failure-at", type=int, default=None)
    ap.add_argument("--kernel-policy", default=None,
                    help='kernel dispatch policy, e.g. "tiled" or '
                         '"schedule=tiled,autotune=off" (see repro.kernels.api)')
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Perfetto/Chrome trace-event JSON of the "
                         "run here (kernel dispatch spans fire at jit trace "
                         "time, so expect one span per compiled program)")
    args = ap.parse_args()
    runtime.setup_compile_cache()
    if args.trace:
        from repro.obs import export as obs_export
        from repro.obs import trace as obs_trace

        rec = obs_trace.start(meta={"tool": "launch.train", "seed": args.seed})
        try:
            out = train_loop(args)
        finally:
            obs_trace.stop()
            obs_export.write(rec, args.trace)
            print(f"wrote trace {args.trace} ({len(rec)} events)")
    else:
        out = train_loop(args)
    print(f"done; final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
