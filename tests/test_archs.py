"""Per-assigned-architecture smoke tests: a REDUCED same-family config runs
one forward + one train step on CPU; output shapes asserted, no NaNs.
(Full configs are exercised only via the dry-run — ShapeDtypeStruct only.)"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, applicable_shapes, get_arch
from repro.data import batch_for
from repro.models import transformer as T
from repro.models.params import init_params
from repro.optim.adafactor import adafactor_init, adafactor_update

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_forward_and_train_step(arch):
    cfg = get_arch(arch).reduced()
    params = init_params(T.param_defs(cfg), KEY)
    B, S = 2, 16
    batch = {k: jnp.asarray(v) for k, v in batch_for(cfg, B, S).items()}

    logits, aux = T.forward_full(
        cfg, params, batch["tokens"],
        patch_embeds=batch.get("patch_embeds"),
        frame_embeds=batch.get("frame_embeds"))
    S_total = S if cfg.family != "vlm" else S
    assert logits.shape == (B, S_total, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    # one optimizer step moves the loss
    opt = adafactor_init(params)

    def loss_of(p):
        l, _ = T.loss_fn(cfg, p, batch)
        return l

    l0, grads = jax.jit(jax.value_and_grad(loss_of))(params)
    assert bool(jnp.isfinite(l0))
    new_params, opt, _ = adafactor_update(params, grads, opt, lr=1e-2)
    l1 = jax.jit(loss_of)(new_params)
    assert bool(jnp.isfinite(l1))
    assert float(l1) != float(l0)
    for leaf in jax.tree.leaves(new_params):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


def _decode_vs_forward(cfg, atol=2e-2):
    """Teacher-forced decode through the donated, in-place ``decode_step``
    against ``forward_full``; returns (full, decoded) logits, or None where
    the family's decode is covered elsewhere."""
    params = init_params(T.param_defs(cfg), KEY)
    B, S = 2, 8
    batch = batch_for(cfg, B, S)
    tokens = jnp.asarray(batch["tokens"])[:, :S]

    kwargs = {}
    if cfg.family == "vlm":
        pytest.skip("vlm decode is prefix-cached; covered by dense path")
    if cfg.family == "encdec":
        frames = jnp.asarray(batch["frame_embeds"]).astype(jnp.bfloat16)
        kwargs["frame_embeds"] = frames
        full, _ = T.forward_full(cfg, params, tokens, **kwargs)
        cache = init_params(T.cache_defs(cfg, B, 16), KEY)
        last, _, _ = T.prefill_with_cache(cfg, params, tokens, cache,
                                          frame_embeds=frames)
        np.testing.assert_allclose(
            np.asarray(full[:, -1].astype(jnp.float32)),
            np.asarray(last), rtol=2e-2, atol=atol)
        return None

    full, _ = T.forward_full(cfg, params, tokens)
    cache = init_params(T.cache_defs(cfg, B, 16), KEY)
    lens = jnp.zeros((B,), jnp.int32)
    step = jax.jit(lambda p, t, c, l: T.decode_step(cfg, p, t, c, l),
                   donate_argnums=2)
    outs = []
    for t in range(tokens.shape[1]):
        lg, cache = step(params, tokens[:, t][:, None], cache, lens)
        lens = lens + 1
        outs.append(lg)
    return (np.asarray(full.astype(jnp.float32)),
            np.asarray(jnp.stack(outs, 1).astype(jnp.float32)))


@pytest.mark.parametrize("arch", ASSIGNED)
def test_decode_matches_prefill(arch):
    """Teacher-forced decode must reproduce full-sequence logits exactly
    (cache correctness for every mixer family)."""
    cfg = get_arch(arch).reduced()
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no MoE drops
    out = _decode_vs_forward(cfg)
    if out is not None:
        np.testing.assert_allclose(*out, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_unrolled_decode_matches_prefill(arch):
    """The unrolled layer loop (scan_layers=False) carries and writes the
    cache the same way as the scanned one (tolerance as in the
    scan-vs-unrolled forward test below: the unrolled lowering fuses
    differently, so bf16 roundings differ by an ulp)."""
    cfg = get_arch(arch).reduced()
    cfg = dataclasses.replace(cfg, capacity_factor=8.0, scan_layers=False,
                              remat="none")
    out = _decode_vs_forward(cfg, atol=0.1)
    if out is not None:
        np.testing.assert_allclose(*out, rtol=2e-2, atol=0.1)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_applicable_shapes_rules(arch):
    cfg = get_arch(arch)
    names = [s.name for s in applicable_shapes(cfg)]
    assert "train_4k" in names and "decode_32k" in names
    if cfg.family in ("ssm", "hybrid"):
        assert "long_500k" in names      # sub-quadratic archs run long ctx
    else:
        assert "long_500k" not in names  # pure attention: skipped (DESIGN.md)


def test_scan_vs_unrolled_equivalence_dense():
    """scan_layers=False (the dry-run cost twin) is mathematically identical
    to the scanned production path (dense arch: strict, one-bf16-ulp tol)."""
    cfg = get_arch("llama3.2-1b").reduced()
    params = init_params(T.param_defs(cfg), KEY)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    a, _ = T.forward_full(cfg, params, tokens)
    cfg2 = dataclasses.replace(cfg, scan_layers=False, remat="none")
    b, _ = T.forward_full(cfg2, params, tokens)
    np.testing.assert_allclose(np.asarray(a.astype(jnp.float32)),
                               np.asarray(b.astype(jnp.float32)),
                               rtol=2e-2, atol=0.1)


def test_scan_vs_unrolled_equivalence_hybrid_moe():
    """Hybrid+MoE arch: bf16 router-logit ties may flip top-k order between
    the two lowerings (different fusion), perturbing the affected tokens —
    assert distribution-level equivalence (>=99% of logits within tol)."""
    cfg = get_arch("jamba-v0.1-52b").reduced()
    params = init_params(T.param_defs(cfg), KEY)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    a, _ = T.forward_full(cfg, params, tokens)
    cfg2 = dataclasses.replace(cfg, scan_layers=False, remat="none")
    b, _ = T.forward_full(cfg2, params, tokens)
    diff = np.abs(np.asarray(a.astype(jnp.float32))
                  - np.asarray(b.astype(jnp.float32)))
    frac_close = float((diff <= 0.1).mean())
    assert frac_close >= 0.99, frac_close
    assert float(diff.max()) < 2.0


def test_chunk_size_invariance():
    """Flash-attention/SSM chunk sizes are performance knobs, not math."""
    base = get_arch("llama3.2-1b").reduced()
    params = init_params(T.param_defs(base), KEY)
    tokens = jax.random.randint(KEY, (2, 32), 0, base.vocab_size)
    ref_l, _ = T.forward_full(base, params, tokens)
    for cq, ckv in [(8, 8), (32, 16), (16, 32)]:
        cfg = dataclasses.replace(base, chunk_q=cq, chunk_kv=ckv)
        got, _ = T.forward_full(cfg, params, tokens)
        np.testing.assert_allclose(np.asarray(ref_l.astype(jnp.float32)),
                                   np.asarray(got.astype(jnp.float32)),
                                   rtol=2e-2, atol=2e-2)


def test_int8_kv_cache_decode_close_to_bf16():
    """§Perf iteration B2: quantized KV decode tracks the bf16 path within
    quantization error (~2% relative at reduced scale)."""
    cfg = get_arch("llama3.2-1b").reduced()
    params = init_params(T.param_defs(cfg), KEY)
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    full, _ = T.forward_full(cfg, params, tokens)
    c2 = dataclasses.replace(cfg, kv_dtype="int8")
    cache = init_params(T.cache_defs(c2, 2, 16), KEY)
    assert cache["pos0"]["k"].dtype == jnp.int8
    lens = jnp.zeros((2,), jnp.int32)
    step = jax.jit(lambda p, t, c, l: T.decode_step(c2, p, t, c, l),
                   donate_argnums=2)
    outs = []
    for t in range(8):
        lg, cache = step(params, tokens[:, t][:, None], cache, lens)
        lens = lens + 1
        outs.append(lg)
    dec = jnp.stack(outs, 1).astype(jnp.float32)
    ref = full.astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(ref - dec)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.05, rel


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen3-moe-30b-a3b",
                                  "jamba-v0.1-52b", "whisper-medium"])
def test_int8_decode_tracks_forward(arch):
    """The int8 cache through the in-place decode of each attention-holding
    family (dense, MoE, hybrid, encoder-decoder) tracks the bf16 forward
    within quantization error, as in the dense case above."""
    cfg = get_arch(arch).reduced()
    cfg = dataclasses.replace(cfg, capacity_factor=8.0, kv_dtype="int8")
    out = _decode_vs_forward(cfg, atol=0.05)
    if out is not None:
        full, dec = out
        rel = float(np.max(np.abs(full - dec)) / np.max(np.abs(full)))
        assert rel < 0.05, rel
