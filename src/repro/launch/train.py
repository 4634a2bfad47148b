"""Training driver: config -> mesh -> sharded state -> fault-tolerant loop.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --shape train_4k --steps 200 --smoke   # reduced config, CPU-runnable

Features exercised here (the production path in miniature):
  * sharded init + optimizer state (FSDP+TP specs from launch/sharding.py)
  * gradient-accumulation microbatching
  * deterministic resumable data pipeline
  * atomic checkpoint/restore with auto-resume, keep-k, async save
  * preemption guard (SIGTERM -> save + clean exit), step retry,
    straggler monitor, heartbeat
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.configs import SHAPES, OptimizerConfig, RunConfig, get_config, smoke
from repro.data.pipeline import DataConfig, make_pipeline
from repro.launch import meshctx, sharding, steps
from repro.launch.mesh import axis_info
from repro.launch.xla_setup import honor_bf16_rounding, use_persistent_cache
from repro.models import model
from repro.optim.optimizer import make_optimizer
from repro.runtime import fault


def build(run: RunConfig, mesh=None, accum: int | None = None):
    """Returns (train_step_jit, state, batch_fn)."""
    cfg = run.model
    optimizer = make_optimizer(run.optimizer)
    dp_size = 1
    if mesh is not None:
        info = axis_info(mesh)
        meshctx.set_mesh(mesh, info["dp_axes"], info["tp_axis"])
        for a in info["dp_axes"]:
            dp_size *= mesh.shape[a]
    if accum is None:
        accum = steps.grad_accum_steps(run, dp_size)
    step_fn = steps.make_train_step(cfg, run, optimizer, accum)

    key = jax.random.PRNGKey(run.seed)
    if mesh is not None:
        params_shape = jax.eval_shape(lambda: model.init_params(key, cfg))
        p_specs = sharding.param_specs(params_shape, cfg, mesh)
        p_shardings = sharding.to_named(p_specs, mesh)
        opt_shape = jax.eval_shape(
            lambda p: optimizer.init(p), params_shape)
        o_specs = sharding.opt_state_specs(opt_shape, p_specs)
        state_shardings = steps.TrainState(
            p_shardings, sharding.to_named(o_specs, mesh))
        with mesh:
            init_fn = jax.jit(
                lambda k: steps.init_train_state(k, cfg, optimizer),
                out_shardings=state_shardings)
            state = init_fn(key)
            step_jit = jax.jit(step_fn, donate_argnums=(0,),
                               out_shardings=(state_shardings, None))
    else:
        state = steps.init_train_state(key, cfg, optimizer)
        step_jit = jax.jit(step_fn, donate_argnums=(0,))
    return step_jit, state, accum


def train_loop(run: RunConfig, total_steps: int, mesh=None,
               accum: int | None = None, log_every: int = 10) -> dict:
    cfg = run.model
    step_jit, state, accum = build(run, mesh, accum)
    pipe = make_pipeline(cfg, run.shape, DataConfig(seed=run.seed))

    # --- auto-resume -------------------------------------------------------
    start_step = 0
    resumed = ckpt.latest_step(run.checkpoint_dir)
    if resumed is not None:
        state, start_step = ckpt.restore(state, run.checkpoint_dir)
        print(f"[resume] from step {start_step}")

    guard = fault.PreemptionGuard().install()
    monitor = fault.StragglerMonitor()
    hb = fault.Heartbeat(f"{run.checkpoint_dir}/heartbeat.json", every_s=10)
    history = []
    t_start = time.time()

    step = start_step
    while step < total_steps:
        batch = pipe.batch_at(step)
        t0 = time.time()
        state, metrics = fault.retry_step(step_jit, state, batch)
        dt = time.time() - t0
        monitor.record(step, dt)
        hb.beat(step)
        if step % log_every == 0 or step == total_steps - 1:
            m = {k: float(np.asarray(v)) for k, v in metrics.items()}
            m.update(step=step, dt=round(dt, 3))
            history.append(m)
            print(f"[train] step={step} loss={m['loss']:.4f} "
                  f"gnorm={m['grad_norm']:.3f} dt={dt:.2f}s", flush=True)
        step += 1
        if guard.requested:
            print("[preempt] SIGTERM received — checkpointing and exiting")
            ckpt.save(state, run.checkpoint_dir, step, keep=run.keep_checkpoints)
            guard.uninstall()
            return {"history": history, "preempted": True, "step": step}
        if step % run.checkpoint_every == 0:
            ckpt.save(state, run.checkpoint_dir, step,
                      keep=run.keep_checkpoints, blocking=False)

    ckpt.save(state, run.checkpoint_dir, step, keep=run.keep_checkpoints)
    guard.uninstall()
    return {
        "history": history,
        "preempted": False,
        "step": step,
        "total_s": time.time() - t_start,
        "stragglers": monitor.stragglers,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--tdvmm", action="store_true",
                    help="run all linears through the TD-VMM layer (QAT)")
    ap.add_argument("--tdvmm-bits", type=int, default=6)
    args = ap.parse_args()
    honor_bf16_rounding()
    use_persistent_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke(cfg)
    if args.tdvmm:
        from repro.core.layers import TDVMMLayerConfig
        cfg = cfg.replace(tdvmm=TDVMMLayerConfig(
            enabled=True, bits=args.tdvmm_bits, weight_bits=args.tdvmm_bits))
    shape = SHAPES[args.shape]
    if args.batch or args.seq:
        import dataclasses
        shape = dataclasses.replace(
            shape,
            global_batch=args.batch or shape.global_batch,
            seq_len=args.seq or shape.seq_len)
    run = RunConfig(model=cfg, shape=shape,
                    optimizer=OptimizerConfig(lr=args.lr, total_steps=args.steps),
                    checkpoint_dir=args.ckpt_dir,
                    checkpoint_every=args.ckpt_every)
    out = train_loop(run, args.steps)
    print(f"[done] steps={out['step']} loss "
          f"{out['history'][0]['loss']:.3f} -> {out['history'][-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
