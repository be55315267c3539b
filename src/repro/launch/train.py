"""Training launcher: checkpoint/restart fault tolerance, straggler
watchdog, elastic resume, optional gradient compression.

CPU-runnable end-to-end driver (examples use it to train a ~small model a
few hundred steps); the same config drives the production mesh on real
hardware — the dry-run proves those lowerings.

  python -m repro.launch.train --arch llama3.2-1b --smoke --steps 200
  python -m repro.launch.train --arch gpt2-m --smoke --steps 100 \
      --ckpt-dir /tmp/ck --fail-at-step 50     # then rerun: resumes at 50
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.data import ByteCorpus, SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.models.params import init_params, shardings_for, param_count
from repro.optim import adamw_init, linear_warmup_cosine
from repro.train import TrainStepConfig, make_train_step


class StragglerWatchdog:
    """Flags steps slower than `factor` x the running median: on multi-host
    deployments this triggers slow-host quarantine + elastic restart; here
    it logs and counts (single-host container)."""

    def __init__(self, factor: float = 3.0, window: int = 50):
        self.factor = factor
        self.window = window
        self.times = []
        self.flagged = 0

    def observe(self, dt: float) -> bool:
        slow = False
        if len(self.times) >= 10:
            med = statistics.median(self.times[-self.window:])
            if dt > self.factor * med:
                self.flagged += 1
                slow = True
        self.times.append(dt)
        return slow


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--data", choices=["synthetic", "bytes"],
                    default="synthetic")
    ap.add_argument("--corpus", default="src")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at-step", type=int, default=0,
                    help="inject a crash (fault-tolerance demo)")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    mesh = make_host_mesh()

    if args.data == "bytes":
        import dataclasses
        cfg = dataclasses.replace(cfg, vocab_size=max(cfg.vocab_size, 256))
        data = ByteCorpus(args.corpus, args.seq, args.batch)
    else:
        data = SyntheticLM(cfg.vocab_size, args.seq, args.batch)

    params = init_params(T.param_defs(cfg), jax.random.PRNGKey(0))
    opt_state = adamw_init(params)
    print(f"[train] arch={cfg.name} params={param_count(T.param_defs(cfg)):,} "
          f"devices={len(jax.devices())}")

    tcfg = TrainStepConfig(
        microbatches=args.microbatches,
        learning_rate=linear_warmup_cosine(args.lr, 20, args.steps),
        compress_grads=args.compress_grads,
    )
    step_fn = jax.jit(make_train_step(cfg, tcfg, mesh))

    start = 0
    mgr = None
    err_state = None
    if args.compress_grads:
        from repro.train import compression
        err_state = compression.init_error_state(params)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        restored = mgr.restore_latest({"params": params, "opt": opt_state})
        if restored:
            params = restored["tree"]["params"]
            opt_state = restored["tree"]["opt"]
            start = restored["step"]
            print(f"[train] resumed from step {start} "
                  f"(elastic: {len(jax.devices())} devices now)")

    wd = StragglerWatchdog()
    losses = []
    for step in range(start, args.steps):
        if args.fail_at_step and step == args.fail_at_step:
            # quiesce the async checkpoint writer first: the injected crash
            # models "die after the last durable checkpoint", not "corrupt
            # the in-flight write" (atomicity has its own test)
            if mgr:
                mgr.wait()
            print(f"[train] INJECTED FAILURE at step {step}", flush=True)
            os._exit(17)
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        t0 = time.time()
        if args.compress_grads:
            params, opt_state, err_state, metrics = step_fn(
                params, opt_state, batch, err_state)
        else:
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        dt = time.time() - t0
        if wd.observe(dt):
            print(f"[watchdog] step {step} straggling: {dt*1e3:.0f}ms")
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"({dt*1e3:.0f}ms/step)", flush=True)
        if mgr and step and step % args.ckpt_every == 0:
            mgr.save(step, {"params": params, "opt": opt_state},
                     {"loss": losses[-1]})
    if mgr:
        mgr.save(args.steps, {"params": params, "opt": opt_state},
                 {"loss": losses[-1]})
        mgr.wait()
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(stragglers flagged: {wd.flagged})")
    return losses


if __name__ == "__main__":
    use_compile_cache()
    main()
