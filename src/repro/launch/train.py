"""Training driver: data pipeline -> sharded train_step -> checkpoint/restart.

Scales from the CPU container (1 device, smoke config) to the production
mesh (same code path — the mesh and shardings come from launch.mesh /
launch.sharding).  Fault tolerance is the runtime.fault loop: deterministic
data + atomic checkpoints = exact replay after restore.

On TPU the whole step — forward *and* backward — runs generated kernels:
``repro.grad`` gives every ``ops`` matmul a custom VJP whose cotangent
GEMMs go through the same searched/tuned pipeline (see
``launch.steps.make_train_step``); warm their plans with
``scripts/search_sweep.py --with-grads`` before a big run.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ck
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import checkpoint as ckpt
from ..configs import SHAPES, get_config
from ..obs import log
from ..configs.base import ShapeConfig
from ..data.pipeline import DataConfig, batch_at
from ..models.api import get_api
from ..optim import AdamWConfig, warmup_cosine
from ..optim import adamw as optim
from ..runtime.fault import FaultTolerantLoop, LoopConfig
from .compile_cache import enable_compile_cache
from .steps import make_train_step


@dataclasses.dataclass
class TrainRun:
    cfg: object
    opt_cfg: AdamWConfig
    data_cfg: DataConfig
    steps: int
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    #: route the loss through repro.capture.optimize so the model's plain
    #: dot_general GEMMs dispatch through the plan-DB pipeline (fwd+bwd);
    #: None = read $REPRO_CAPTURE
    capture: Optional[bool] = None


def train(run: TrainRun, params=None, verbose: bool = True):
    cfg = run.cfg
    api = get_api(cfg)
    if params is None:
        params, _ = api.init(cfg, jax.random.key(0))
    opt_state = optim.init(params, run.opt_cfg)
    schedule = warmup_cosine(
        warmup=min(100, run.steps // 10 + 1), total=run.steps
    )
    step_fn = jax.jit(make_train_step(
        cfg, run.opt_cfg, lr_schedule=schedule, capture=run.capture
    ))

    mgr = (
        ckpt.CheckpointManager(run.ckpt_dir, keep=3) if run.ckpt_dir else None
    )
    start_step = 0
    if run.ckpt_dir and ckpt.latest_step(run.ckpt_dir) is not None:
        (params, opt_state), manifest = ckpt.restore(
            run.ckpt_dir, (params, opt_state)
        )
        start_step = manifest["step"]
        if verbose:
            log.info("restore", f"resuming from step {start_step}")

    losses = []
    state = (params, opt_state)

    def one_step(step, state):
        params, opt_state = state
        batch = {
            k: jnp.asarray(v) for k, v in batch_at(run.data_cfg, step).items()
        }
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if verbose and step % run.log_every == 0:
            log.info(
                None,
                f"step {step:5d} loss {float(metrics['loss']):.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f}",
                flush=True,
            )
        return (params, opt_state)

    def save_fn(step, state):
        if mgr:
            mgr.save_async(step, state, extra={"step": step})

    def restore_fn():
        (p, o), manifest = ckpt.restore(run.ckpt_dir, state)
        return manifest["step"], (p, o)

    loop = FaultTolerantLoop(
        step_fn=one_step,
        save_fn=save_fn,
        restore_fn=restore_fn,
        config=LoopConfig(checkpoint_every=run.ckpt_every),
    )
    state = loop.run(state, start_step, run.steps - start_step)
    if mgr:
        mgr.close()
    return state, losses, loop.report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--moments", default="float32",
                    choices=["float32", "bfloat16", "int8"])
    ap.add_argument("--capture", action="store_true",
                    help="capture the whole model: harvest its plain "
                         "dot_general GEMMs and dispatch the eligible "
                         "ones through the plan-DB pipeline "
                         "(repro.capture; also $REPRO_CAPTURE=1)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    run = TrainRun(
        cfg=cfg,
        opt_cfg=AdamWConfig(lr=args.lr, moments_dtype=args.moments),
        data_cfg=DataConfig(
            vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch
        ),
        steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        capture=args.capture or None,
    )
    t0 = time.time()
    _, losses, report = train(run)
    dt = time.time() - t0
    log.info(
        "train",
        f"{args.steps} steps in {dt:.1f}s; "
        f"loss {losses[0]:.3f} -> {np.mean(losses[-5:]):.3f}; "
        f"stragglers={len(report.straggler_events)}"
    )


if __name__ == "__main__":
    main()
