"""Shared building blocks: norms, RoPE, dense MLP, embeddings, loss."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef
from repro.sharding.axes import constrain


# --------------------------------------------------------------------------- #
# Norms (paper: VU executes two-phase LayerNorm; kernels/layernorm.py is the
# Pallas twin — this is the XLA path / oracle)
# --------------------------------------------------------------------------- #
def norm_defs(cfg: ModelConfig, stacked: Optional[int] = None) -> dict:
    if cfg.norm == "np_layernorm":
        return {}
    shape = (cfg.d_model,)
    axes: tuple = ("d_model",)
    if stacked is not None:
        shape = (stacked,) + shape
        axes = ("layers",) + axes
    out = {"scale": ParamDef(shape, axes, "ones")}
    if cfg.norm == "layernorm":
        out["bias"] = ParamDef(shape, axes, "zeros")
    return out


def apply_norm(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        if cfg.norm == "layernorm":
            y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
        # np_layernorm (OLMo): no affine params
    return y.astype(x.dtype)


def activation(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.act == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


# --------------------------------------------------------------------------- #
# RoPE (f32 math)
# --------------------------------------------------------------------------- #
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, head_dim); positions: broadcastable to (..., seq)."""
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)                     # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs        # (..., seq, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Dense (SwiGLU / GELU) MLP — the paper's FFN
# --------------------------------------------------------------------------- #
def mlp_defs(cfg: ModelConfig, stacked: Optional[int] = None) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    lead = () if stacked is None else (stacked,)
    la = () if stacked is None else ("layers",)
    out = {
        "wi": ParamDef(lead + (d, f), la + ("d_model", "d_ff")),
        "wo": ParamDef(lead + (f, d), la + ("d_ff", "d_model")),
    }
    if cfg.act == "silu":  # gated
        out["wg"] = ParamDef(lead + (d, f), la + ("d_model", "d_ff"))
    return out


def apply_mlp(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """x: (B, S, d). Column-parallel wi/wg, row-parallel wo -> one all-reduce,
    exactly the paper's intra-layer (column-wise) FC partitioning (§5.1)."""
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if "wg" in p:
        h = activation(cfg, jnp.einsum("bsd,df->bsf", x, p["wg"])) * h
    else:
        h = activation(cfg, h)
    h = constrain(h, ("batch", "seq", "d_ff"))
    out = jnp.einsum("bsf,fd->bsd", h, p["wo"])
    return constrain(out, ("batch", "seq", "d_model"))


# --------------------------------------------------------------------------- #
# Embedding / LM head / loss
# --------------------------------------------------------------------------- #
def embed_defs(cfg: ModelConfig) -> dict:
    out = {"tok": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "d_model"),
                           "small_normal")}
    if not cfg.tie_embeddings:
        out["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size),
                                  ("d_model", "vocab"))
    return out


def embed_tokens(p: dict, tokens: jax.Array, d_model: int) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0)
    return constrain(x, ("batch", "seq", "d_model"))


def lm_logits(p: dict, x: jax.Array, tie: bool) -> jax.Array:
    w = p["tok"].T if tie else p["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return constrain(logits, ("batch", "seq", "vocab"))


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean NLL. Vocab may be sharded: the correct-class logit is extracted
    with an iota==label mask (no gather across a sharded dim), and logsumexp
    reduces over the sharded axis (XLA inserts the all-reduce)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    correct = jnp.sum(jnp.where(iota == labels[..., None], lf, 0.0), axis=-1)
    nll = lse - correct
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)
