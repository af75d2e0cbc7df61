"""End-to-end training driver.

Runs REAL steps (CPU: reduced config; TPU: full config) with the whole
production substrate engaged: deterministic sharded data pipeline,
AdamW/adafactor, async atomic checkpointing with retention, crash/resume
(--preempt-at simulates a SIGTERM mid-run; rerunning with the same
--ckpt-dir resumes from the newest checkpoint), and optional int8
gradient compression on the pod boundary.

``--compress-grads`` routes through the ``compress_fn`` hook of
``make_train_step`` and engages ONLY when the gradient reduction
actually crosses a pod (DCN) boundary (``--pods > 1``): intra-pod
gradients ride ICI and stay uncompressed -- the seed wrapped the whole
optimizer, quantizing every reduction regardless of the link it used.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.ckpt import Checkpointer, latest_step
from repro.configs import get_arch, reduced
from repro.data.lm_pipeline import LMPipeline, PipelineSpec
from repro.dist.compression import make_pod_compress_fn
from repro.launch.compile_cache import enable_compile_cache
from repro.models.blocks import Ctx
from repro.models.lm import LM
from repro.train import make_optimizer, make_train_step
from repro.train.train_step import TrainState, init_train_state


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized same-family config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--preempt-at", type=int, default=-1,
                    help="simulate preemption after this step")
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8-compress the pod-boundary gradient "
                         "reduction (no-op unless --pods > 1)")
    ap.add_argument("--pods", type=int, default=1,
                    help="pods the gradient all-reduce crosses; intra-pod "
                         "gradients are never compressed")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg, grad_accum=1)
    if args.seq % max(cfg.ssm_chunk, 1):
        cfg = dataclasses.replace(cfg, ssm_chunk=min(cfg.ssm_chunk,
                                                     args.seq))
    model = LM(cfg)
    ctx = Ctx(cfg=cfg)
    opt = make_optimizer(cfg, base_lr=args.lr, warmup=10,
                         total=max(args.steps, 100))
    compress_fn = None
    if args.compress_grads:
        compress_fn = make_pod_compress_fn(n_pods=args.pods)
        print("grad compression:",
              "pod-boundary int8" if compress_fn is not None
              else "off (no pod boundary to compress)")
    step_fn = jax.jit(make_train_step(model, opt, ctx=ctx,
                                      grad_accum=cfg.grad_accum,
                                      compress_fn=compress_fn))
    pipe = LMPipeline(PipelineSpec(cfg.vocab_size, args.seq, args.batch,
                                   seed=args.seed))
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None

    start = 0
    if ckpt is not None and latest_step(args.ckpt_dir) is not None:
        like = jax.eval_shape(
            lambda: init_train_state(model, opt, jax.random.PRNGKey(0)))
        state, start = ckpt.restore(like)
        print(f"resumed from step {start}")
    else:
        state = init_train_state(model, opt,
                                 jax.random.PRNGKey(args.seed))

    frontend = None
    if cfg.frontend != "none":
        fd = cfg.frontend_dim or cfg.d_model
        frontend = jnp.zeros((args.batch, cfg.frontend_tokens, fd),
                             jnp.dtype(cfg.dtype))

    losses = []
    t0 = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in
                 pipe.batch_at(step).items()}
        if frontend is not None:
            batch["frontend"] = frontend
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):8.3f} "
                  f"{(time.time() - t0):6.1f}s")
        if ckpt is not None and (step + 1) % args.ckpt_every == 0:
            ckpt.save(state, step + 1)
        if args.preempt_at >= 0 and step + 1 >= args.preempt_at:
            if ckpt is not None:
                ckpt.wait()
            print(f"PREEMPTED at step {step + 1} (simulated)")
            return {"final_loss": losses[-1], "steps_done": step + 1,
                    "losses": losses, "preempted": True}
    if ckpt is not None:
        ckpt.save(state, args.steps)
        ckpt.wait()
    print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return {"final_loss": losses[-1], "first_loss": losses[0],
            "steps_done": args.steps, "losses": losses,
            "preempted": False,
            "grad_compression": ("pod-boundary"
                                 if compress_fn is not None else "off")}


if __name__ == "__main__":
    main()
