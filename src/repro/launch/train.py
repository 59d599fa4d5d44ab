"""Fault-tolerant training driver.

Production loop structure, exercised end-to-end on CPU with reduced
configs (examples/train_e2e.py) and designed for the 256/512-chip meshes:

* **Checkpoint/restart** — async CheckpointManager with atomic publish;
  on start, resumes from the latest step (data pipeline state rides in the
  manifest, so the token stream continues bit-exactly).
* **Elastic resharding** — restore maps every leaf onto the CURRENT mesh's
  NamedShardings; a checkpoint taken on mesh A restores on mesh B.
* **Straggler mitigation** — per-step wall-time EWMA; a step slower than
  ``straggler_factor`` x EWMA is logged and counted (on a real pod this
  feeds the reschedule/deadline logic; here it drives the log + metrics).
* **Failure injection** — ``--fail-at-step N`` raises mid-run; rerunning
  the same command resumes from the last checkpoint (tests do exactly
  this), proving the restart path.

Run (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
      --reduced --steps 20 --ckpt-dir /tmp/ckpt --checkpoint-every 5
"""
from __future__ import annotations

import argparse
import json
import os
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import SHAPES, ShapeConfig, get_arch
from repro.data.pipeline import SyntheticDataset
from repro.models import registry
from repro.models.param import init_params
from repro.optim.adamw import AdamWConfig
from repro.parallel.jax_compat import use_compile_cache
from repro.parallel.sharding import BASELINE, RULE_VARIANTS, use_rules
from repro.train.steps import TrainState, make_train_step


@dataclass
class TrainLoopConfig:
    arch: str = "qwen2.5-3b"
    reduced: bool = True
    steps: int = 20
    seq_len: int = 64
    global_batch: int = 8
    microbatches: int = 1
    ckpt_dir: str = ""
    checkpoint_every: int = 10
    keep: int = 3
    seed: int = 0
    lr: float = 3e-4
    straggler_factor: float = 2.0
    fail_at_step: int = -1
    grad_compression: str | None = None
    rules: str = "baseline"
    log_every: int = 1


def run(cfg_loop: TrainLoopConfig) -> dict:
    arch = get_arch(cfg_loop.arch)
    cfg = arch.reduced() if cfg_loop.reduced else arch
    shape = ShapeConfig("train_custom", cfg_loop.seq_len,
                        cfg_loop.global_batch, "train")
    opt = AdamWConfig(lr_peak=cfg_loop.lr, warmup_steps=2,
                      total_steps=max(10, cfg_loop.steps))
    rules = RULE_VARIANTS[cfg_loop.rules]
    data = SyntheticDataset(cfg, shape, seed=cfg_loop.seed)
    step_fn = make_train_step(
        cfg, opt, microbatches=cfg_loop.microbatches,
        grad_compression=cfg_loop.grad_compression)

    ckpt = CheckpointManager(cfg_loop.ckpt_dir, keep=cfg_loop.keep) \
        if cfg_loop.ckpt_dir else None

    with use_rules(rules):
        params = init_params(registry.param_specs(cfg),
                             jax.random.PRNGKey(cfg_loop.seed))
        state = TrainState.create(
            params, opt, grad_compression=cfg_loop.grad_compression)
        start_step = 0
        if ckpt is not None and ckpt.latest_step() is not None:
            state, extras = ckpt.restore(state)
            start_step = int(extras.get("data_state", {}).get("step", 0))
            print(f"[train] resumed from checkpoint step {start_step}")
        jstep = jax.jit(step_fn, donate_argnums=(0,))

        ewma = None
        stragglers = 0
        losses = []
        for step in range(start_step, cfg_loop.steps):
            if step == cfg_loop.fail_at_step:
                raise RuntimeError(
                    f"[train] injected failure at step {step}")
            t0 = time.time()
            batch = data.batch(step)
            state, metrics = jstep(state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            if ewma is None:
                ewma = dt
            if dt > cfg_loop.straggler_factor * ewma and step > start_step:
                stragglers += 1
                print(f"[train] step {step}: STRAGGLER {dt:.3f}s "
                      f"(ewma {ewma:.3f}s) — deterministic batch would be "
                      f"re-issued on a spare")
            ewma = 0.9 * ewma + 0.1 * dt
            losses.append(loss)
            if step % cfg_loop.log_every == 0:
                print(f"[train] step {step}: loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} {dt*1e3:.0f}ms")
            if (ckpt is not None and cfg_loop.checkpoint_every > 0
                    and (step + 1) % cfg_loop.checkpoint_every == 0):
                ckpt.save(step + 1, state,
                          extras={"data_state": data.state(step + 1),
                                  "arch": cfg.name})
        if ckpt is not None:
            ckpt.save(cfg_loop.steps, state,
                      extras={"data_state": data.state(cfg_loop.steps),
                              "arch": cfg.name}, blocking=True)
    return {"losses": losses, "stragglers": stragglers,
            "final_loss": losses[-1] if losses else None}


def main(argv=None):
    ap = argparse.ArgumentParser()
    for f in ("arch", "ckpt_dir", "grad_compression", "rules"):
        ap.add_argument(f"--{f.replace('_', '-')}", default=None)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    for f in ("steps", "seq_len", "global_batch", "microbatches",
              "checkpoint_every", "seed", "fail_at_step"):
        ap.add_argument(f"--{f.replace('_', '-')}", type=int, default=None)
    args = ap.parse_args(argv)
    use_compile_cache()
    cfg = TrainLoopConfig()
    for k, v in vars(args).items():
        if v is not None:
            setattr(cfg, k, v)
    out = run(cfg)
    print(f"[train] done: final_loss={out['final_loss']:.4f} "
          f"stragglers={out['stragglers']}")


if __name__ == "__main__":
    main()
