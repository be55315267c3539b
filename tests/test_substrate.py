"""Optimizers, data pipeline, checkpointing, serving engine, SSM scans."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import CheckpointManager, latest_step, save_checkpoint
from repro.configs import get_arch
from repro.data import ByteCorpus, SyntheticLM
from repro.models import transformer as T
from repro.models.params import init_params
from repro.models.ssm import chunked_linear_scan
from repro.optim import adamw_init, adamw_update
from repro.optim.adafactor import adafactor_init, adafactor_update
from repro.serve import ServeConfig, ServeEngine
from repro.trace import TraceRecorder

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------- #
# chunked linear scan (the SSM substrate)
# --------------------------------------------------------------------------- #
@given(T_=st.sampled_from([1, 4, 16, 64]), chunk=st.sampled_from([1, 4, 8, 64]),
       d=st.integers(1, 8), seed=st.integers(0, 50))
@settings(max_examples=40, deadline=None)
def test_chunked_linear_scan_matches_loop(T_, chunk, d, seed):
    if T_ % min(chunk, T_) != 0:
        return
    key = jax.random.PRNGKey(seed)
    a = jax.random.uniform(key, (T_, d), minval=0.1, maxval=1.0)
    b = jax.random.normal(jax.random.PRNGKey(seed + 1), (T_, d))
    h0 = jax.random.normal(jax.random.PRNGKey(seed + 2), (d,))
    h_all, h_fin = chunked_linear_scan(a, b, h0, chunk)
    h = h0
    for t in range(T_):
        h = a[t] * h + b[t]
        np.testing.assert_allclose(np.asarray(h_all[t]), np.asarray(h),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_fin), np.asarray(h),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# optimizers: both drive a quadratic to its minimum
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("kind", ["adamw", "adafactor"])
def test_optimizer_converges(kind):
    target = jnp.array([[1.0, -2.0], [0.5, 3.0]])
    params = {"w": jnp.zeros((2, 2))}
    state = adamw_init(params) if kind == "adamw" else adafactor_init(params)

    def loss(p):
        return jnp.sum(jnp.square(p["w"] - target))

    for _ in range(400):
        g = jax.grad(loss)(params)
        if kind == "adamw":
            params, state, _ = adamw_update(params, g, state, 0.05)
        else:
            params, state, _ = adafactor_update(params, g, state, 0.05)
    assert float(loss(params)) < 0.05


def test_adafactor_state_is_tiny():
    from repro.models.params import param_bytes
    from repro.optim.adafactor import adafactor_state_defs
    defs = T.param_defs(get_arch("kimi-k2-1t-a32b"))
    st_defs = adafactor_state_defs(defs)
    # factored second moment: < 1% of parameter memory
    assert param_bytes(st_defs) < 0.01 * param_bytes(defs) * 8


# --------------------------------------------------------------------------- #
# data pipeline
# --------------------------------------------------------------------------- #
def test_synthetic_deterministic_and_learnable_structure():
    d1 = SyntheticLM(256, 32, 4, seed=1)
    d2 = SyntheticLM(256, 32, 4, seed=1)
    b1, b2 = d1.batch(7), d2.batch(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(d1.batch(8)["tokens"], b1["tokens"])
    # labels are the shifted stream
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_byte_corpus_reads_this_repo():
    data = ByteCorpus("src", 64, 2)
    b = data.batch(0)
    assert b["tokens"].shape == (2, 64)
    assert b["tokens"].max() < 256


# --------------------------------------------------------------------------- #
# checkpointing: atomicity, retention, restore
# --------------------------------------------------------------------------- #
def test_checkpoint_atomic_and_retention():
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.float32)}}
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3, 4, 5):
            save_checkpoint(d, s, tree, keep=2)
        assert latest_step(d) == 5
        steps = sorted(int(n.split("_")[1]) for n in os.listdir(d)
                       if n.startswith("step_"))
        assert steps == [4, 5]                       # retention
        assert not any(n.endswith(".tmp") for n in os.listdir(d))  # atomic


def test_checkpoint_roundtrip_bf16_exact():
    tree = {"w": (jax.random.normal(KEY, (8, 8)) * 3).astype(jnp.bfloat16),
            "step": jnp.array(7, jnp.int32)}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(7, tree)
        mgr.wait()
        out = mgr.restore_latest(tree)
        assert out["step"] == 7
        for a, b in zip(jax.tree.leaves(out["tree"]), jax.tree.leaves(tree)):
            assert a.dtype == b.dtype
            assert bool(jnp.array_equal(a, b))


# --------------------------------------------------------------------------- #
# serving engine
# --------------------------------------------------------------------------- #
def test_serve_continuous_batching_more_requests_than_slots():
    cfg = get_arch("llama3.2-1b").reduced()
    params = init_params(T.param_defs(cfg), KEY)
    rec = TraceRecorder()
    eng = ServeEngine(cfg, params, ServeConfig(max_slots=2, max_len=64),
                      recorder=rec)
    rng = np.random.default_rng(0)
    rids = [eng.add_request(rng.integers(0, cfg.vocab_size, 3),
                            max_new_tokens=4) for _ in range(5)]
    res = eng.run_until_done()
    assert sorted(res) == sorted(rids)
    assert all(len(v) == 4 for v in res.values())
    routes = [e["route"] for e in rec.events
              if e["type"] in ("prefill", "decode")]
    assert routes and all(r["active"] <= 2 for r in routes)


def test_serve_greedy_deterministic():
    cfg = get_arch("llama3.2-1b").reduced()
    params = init_params(T.param_defs(cfg), KEY)
    outs = []
    for _ in range(2):
        eng = ServeEngine(cfg, params, ServeConfig(max_slots=1, max_len=32))
        eng.add_request([5, 6, 7], max_new_tokens=6)
        outs.append(list(eng.run_until_done().values())[0])
    assert outs[0] == outs[1]
