"""Dense pre-norm decoder: OLMo and Phi-3 are of this architecture.

    h = x + attention(norm1(x));  x' = h + mlp(norm2(h))
    logits = final_norm(x_L) @ head     (head: the embedding, if tied)

Attention: RoPE on the first and second half of each head, grouped-query
causal attention (``num_key_value_heads`` at most ``num_attention_heads``).
MLP: SwiGLU, ``(silu(h W_gate) * h W_up) W_down``. The configuration
file's ``norm`` states the norm and its epsilon:

    layernorm_nonparametric   OLMo's LayerNorm, no scale and no bias:
                              (x - mean(x)) * rsqrt(var(x) + eps)
    rmsnorm                   x * rsqrt(mean(x^2) + eps) * scale, with a
                              scale per layer norm and one before the head

Weight leaves, in the order they are drawn (``weights.py``):

    embed          (vocab, d)            embed   token embedding
    lm_head        (d, vocab)            matrix  untied head only
    layers/wq      (L, d, heads, hd)     matrix  layers/wk, wv (L, d, kv_heads, hd)
    layers/wo      (L, heads, hd, d)     matrix
    layers/w_gate, w_up (L, d, ff)       matrix  layers/w_down (L, ff, d)
    layers/norm1, norm2 (L, d)           scale   rmsnorm only
    final_norm     (d,)                  scale   rmsnorm only

The scales come after every other leaf, so a configuration without them
draws the same weights from a seed as before they existed.
"""
from __future__ import annotations

import copy
import functools

import jax
import jax.numpy as jnp

import flops
from reference import attention, mm, rope

# the configuration file's norm kind -> the program's ``ModelConfig.norm``
PROGRAM_NORM = {"layernorm_nonparametric": "np_layernorm",
                "rmsnorm": "rmsnorm"}

# the CPU rehearsal's model sizes; the KV heads follow (4 for MHA, else 2)
REHEARSAL = {"hidden_size": 256, "intermediate_size": 512,
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "vocab_size": 512}


def _kind(conf: dict) -> str:
    kind = conf["norm"]["kind"]
    if kind not in PROGRAM_NORM:
        raise ValueError(f"dense_decoder has no {kind!r} norm; known: "
                         f"{sorted(PROGRAM_NORM)}")
    return kind


def dims(conf: dict) -> dict:
    """The sizes every other function reads, from the published keys."""
    c = conf["config"]
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "heads": h,
            "kv_heads": c.get("num_key_value_heads") or h,
            "hd": c.get("head_dim") or d // h,
            "ff": c["intermediate_size"], "layers": c["num_hidden_layers"],
            "vocab": c["vocab_size"],
            "tied": bool(c.get("tie_word_embeddings", False)),
            "theta": float(c.get("rope_theta", 10000.0))}


def shapes(conf: dict) -> dict:
    m = dims(conf)
    d, h, kh, hd, f, L, V = (m["d"], m["heads"], m["kv_heads"], m["hd"],
                             m["ff"], m["layers"], m["vocab"])
    out = {"embed": ((V, d), None, "embed")}
    if not m["tied"]:
        out["lm_head"] = ((d, V), d, "matrix")
    out.update({
        "layers/wq": ((L, d, h, hd), d, "matrix"),
        "layers/wk": ((L, d, kh, hd), d, "matrix"),
        "layers/wv": ((L, d, kh, hd), d, "matrix"),
        "layers/wo": ((L, h, hd, d), h * hd, "matrix"),
        "layers/w_gate": ((L, d, f), d, "matrix"),
        "layers/w_up": ((L, d, f), d, "matrix"),
        "layers/w_down": ((L, f, d), f, "matrix"),
    })
    if _kind(conf) == "rmsnorm":
        out["layers/norm1"] = ((L, d), None, "scale")
        out["layers/norm2"] = ((L, d), None, "scale")
        out["final_norm"] = ((d,), None, "scale")
    return out


def program_fields(conf: dict) -> dict:
    m = dims(conf)
    return dict(
        norm=PROGRAM_NORM[_kind(conf)], act=conf["config"]["hidden_act"],
        norm_eps=float(conf["norm"]["eps"]),
        num_layers=m["layers"], d_model=m["d"], num_heads=m["heads"],
        num_kv_heads=m["kv_heads"], head_dim=m["hd"], d_ff=m["ff"],
        vocab_size=m["vocab"], rope_theta=m["theta"],
        tie_embeddings=m["tied"], dtype=conf["config"]["torch_dtype"])


def _scale(leaf) -> dict:
    return {} if leaf is None else {"scale": leaf}


def program_tree(w: dict) -> dict:
    L = w["layers"]
    block = {
        "attn": {"wq": L["wq"], "wk": L["wk"], "wv": L["wv"], "wo": L["wo"]},
        "ffn": {"wg": L["w_gate"], "wi": L["w_up"], "wo": L["w_down"]},
        "norm1": _scale(L.get("norm1")), "norm2": _scale(L.get("norm2")),
    }
    embed = {"tok": w["embed"]}
    if "lm_head" in w:
        embed["lm_head"] = w["lm_head"]
    return {"embed": embed, "blocks": {"pos0": block},
            "final_norm": _scale(w.get("final_norm"))}


# ---- the reference ------------------------------------------------------ #
def _norm(x, kind, eps, scale):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
        return y * scale.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


@functools.partial(jax.jit, static_argnames=("kind", "eps", "theta", "mode"))
def _layer(x, layers, i, *, kind, eps, theta, mode):
    """One decoder layer of the reference. x: (R, S, d) float32."""
    w = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
         for k, v in layers.items()}
    h = _norm(x, kind, eps, w.get("norm1"))
    q = rope(mm("rsd,dhk->rshk", h, w["wq"], mode), theta)
    k = rope(mm("rsd,dhk->rshk", h, w["wk"], mode), theta)
    v = mm("rsd,dhk->rshk", h, w["wv"], mode)
    x = x + mm("rshk,hkd->rsd", attention(q, k, v, mode), w["wo"], mode)
    h = _norm(x, kind, eps, w.get("norm2"))
    g = mm("rsd,df->rsf", h, w["w_gate"], mode)
    u = mm("rsd,df->rsf", h, w["w_up"], mode)
    return x + mm("rsf,fd->rsd", jax.nn.silu(g) * u, w["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("kind", "eps", "mode"))
def _head(x, pos, head, scale, *, kind, eps, mode):
    """Logits (R, P, V) at positions ``pos`` (R, P) of x (R, S, d)."""
    sel = jnp.take_along_axis(x, pos[..., None], axis=1)
    h = _norm(sel, kind, eps, scale)
    return mm("rpd,dv->rpv", h, head, mode)


def _static(conf: dict, mode: str) -> dict:
    if conf["config"]["hidden_act"] != "silu":
        raise ValueError("dense_decoder's reference has only SwiGLU (silu); "
                         f"the configuration states "
                         f"{conf['config']['hidden_act']!r}")
    return dict(kind=_kind(conf), eps=float(conf["norm"]["eps"]), mode=mode)


def logits_at(conf: dict, w: dict, tokens, pos, mode: str = "f32"):
    """Reference logits (R, P, V) float32 on the device. tokens (R, S)
    int32 with S a multiple of the query block (the tail is padding, which
    causal attention never lets an earlier position see); pos (R, P)."""
    m = dims(conf)
    kw = _static(conf, mode)
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for i in range(m["layers"]):
        x = _layer(x, w["layers"], i, theta=m["theta"], **kw)
    head = w["embed"].T if m["tied"] else w["lm_head"]
    return _head(x, jnp.asarray(pos), head, w.get("final_norm"), **kw)


def reference_layer(conf: dict, w: dict, rows: int, seq_len: int,
                    mode: str):
    """``_layer`` lowered for abstract weights ``w`` at rows x seq_len, on
    the device that holds ``w``."""
    on = w["embed"].sharding
    x = jax.ShapeDtypeStruct((rows, seq_len, dims(conf)["d"]), jnp.float32,
                             sharding=on)
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=on)
    return _layer.lower(x, w["layers"], i, theta=dims(conf)["theta"],
                        **_static(conf, mode))


# ---- counts ------------------------------------------------------------- #
class Counts(flops.Counts):
    """Dense products of every layer (the head too in decode), causal
    attention over the positions before a token, and bf16 weights and
    keys and values; an RMSNorm's scales are read with the weights."""

    def __init__(self, conf: dict):
        m = dims(conf)
        d, h, kh, hd, f, L = (m["d"], m["heads"], m["kv_heads"], m["hd"],
                              m["ff"], m["layers"])
        layer_params = d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * f
        head_params = d * m["vocab"]
        scales = _kind(conf) == "rmsnorm"
        super().__init__(
            token_flops=2 * L * layer_params,
            head_flops=2 * head_params,
            attn_flops_per_key=4 * L * h * hd,
            weight_bytes=2 * L * (layer_params + (2 * d if scales else 0)),
            head_bytes=2 * (head_params + (d if scales else 0)),
            kv_bytes_per_token=2 * L * kh * hd * 2)


def rehearsal(conf: dict) -> dict:
    conf = copy.deepcopy(conf)
    c = conf["config"]
    mha = (c.get("num_key_value_heads") or c["num_attention_heads"]) == \
        c["num_attention_heads"]
    c.pop("head_dim", None)
    c.update(REHEARSAL)
    c["num_key_value_heads"] = 4 if mha else 2
    c["torch_dtype"] = "bfloat16"
    return conf
