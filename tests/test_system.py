"""End-to-end system behaviour: training convergence, fault-tolerant
restart, gradient compression, and the serve->PAS integration."""
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.data import SyntheticLM
from repro.models import transformer as T
from repro.models.params import init_params
from repro.optim import adamw_init
from repro.train import TrainStepConfig, make_train_step

KEY = jax.random.PRNGKey(0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def _train(cfg, steps=80, microbatches=1, lr=2e-3):
    params = init_params(T.param_defs(cfg), KEY)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(
        cfg, TrainStepConfig(microbatches=microbatches,
                             learning_rate=lambda s: lr)))
    data = SyntheticLM(cfg.vocab_size, 32, 8)
    losses = []
    for i in range(steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    return losses


def test_training_reduces_loss():
    cfg = get_arch("llama3.2-1b").reduced()
    losses = _train(cfg, steps=80)
    first = np.mean(losses[:8])
    last = np.mean(losses[-8:])
    assert last < first - 0.15, (first, last)
    assert np.isfinite(losses).all()


def test_microbatching_matches_full_batch():
    """Gradient accumulation must be numerically equivalent (f32 accum)."""
    cfg = get_arch("llama3.2-1b").reduced()
    l1 = _train(cfg, steps=12, microbatches=1)
    l2 = _train(cfg, steps=12, microbatches=4)
    np.testing.assert_allclose(l1, l2, rtol=2e-2, atol=2e-2)


@pytest.mark.slow
def test_fault_tolerant_restart():
    """Kill training mid-run (injected failure), relaunch, verify resume
    from the checkpoint and completion."""
    with tempfile.TemporaryDirectory() as d:
        args = [sys.executable, "-m", "repro.launch.train",
                "--arch", "llama3.2-1b", "--smoke", "--steps", "60",
                "--batch", "4", "--seq", "32",
                "--ckpt-dir", d, "--ckpt-every", "20",
                "--fail-at-step", "45", "--log-every", "20"]
        r1 = subprocess.run(args, capture_output=True, text=True, env=ENV)
        assert r1.returncode == 17, r1.stderr[-2000:]      # injected crash
        assert "INJECTED FAILURE" in r1.stdout
        # relaunch without the failure: must resume from step 40
        args2 = [a for a in args if a not in ("--fail-at-step", "45")]
        r2 = subprocess.run(args2, capture_output=True, text=True, env=ENV)
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert "resumed from step 40" in r2.stdout
        assert "done:" in r2.stdout


def test_compressed_allreduce_error_feedback():
    """int8 EF all-reduce: quantized mean close to the true mean, and the
    error buffer carries the residual so the BIAS vanishes over steps."""
    from jax.sharding import Mesh
    from repro.train import compression
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(0, 1, (64, 64)),
                          jnp.float32)}
    err = compression.init_error_state(g)
    acc_true = jnp.zeros((64, 64))
    acc_q = jnp.zeros((64, 64))
    for i in range(30):
        gi = jax.tree.map(lambda x: x * (1 + 0.01 * i), g)
        out, err = compression.compressed_grad_allreduce(gi, err, mesh)
        acc_true += gi["w"]
        acc_q += out["w"]
    # single-step error bounded by quantization step
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127
    assert float(jnp.max(jnp.abs(out["w"] - gi["w"]))) < 2 * scale
    # accumulated error stays bounded (error feedback: no drift)
    assert float(jnp.max(jnp.abs(acc_q - acc_true))) < 30 * scale


def test_pas_serving_integration():
    """The serving loop consults the PAS cost model every step."""
    from repro.serve import ServeConfig, ServeEngine
    from repro.trace import TraceRecorder
    cfg = get_arch("llama3.2-1b").reduced()
    params = init_params(T.param_defs(cfg), KEY)
    rec = TraceRecorder()
    eng = ServeEngine(cfg, params, ServeConfig(max_slots=2, max_len=32),
                      recorder=rec)
    eng.add_request([1, 2], max_new_tokens=3)
    eng.run_until_done()
    routes = [e["route"] for e in rec.events
              if e["type"] in ("prefill", "decode")]
    assert routes
    assert all(r["gemv_path"] for r in routes)  # tiny batches -> GEMV
