"""Random weights for a configuration, made on the device from the seed.

The benchmark makes the weights itself, in one jitted program, in the type
they are served in (bfloat16), so that the plain reference and the system
under test read the same numbers and neither depends on the other. The
layout is the plain one of the published architecture, stacked over
layers; ``system.py`` rearranges it into the program's parameter tree.

    embed        (vocab, d)              token embedding (and the head, if tied)
    lm_head      (d, vocab)              untied output head
    layers/wq    (L, d, heads, hd)       layers/wk, wv (L, d, kv_heads, hd)
    layers/wo    (L, heads, hd, d)
    layers/w_gate, w_up (L, d, ff)       layers/w_down (L, ff, d)

Matrices are normal with std 1/sqrt(fan-in); embeddings normal with std
0.02. The served configurations normalize without parameters (OLMo's
LayerNorm), so there are no norm leaves.
"""
from __future__ import annotations

import numpy as np


def dims(c: dict) -> dict:
    """The sizes every other module reads, from a published config dict."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "heads": h,
            "kv_heads": c.get("num_key_value_heads") or h,
            "hd": c.get("head_dim") or d // h,
            "ff": c["intermediate_size"], "layers": c["num_hidden_layers"],
            "vocab": c["vocab_size"],
            "tied": bool(c.get("tie_word_embeddings", False)),
            "theta": float(c.get("rope_theta", 10000.0))}


def shapes(c: dict) -> dict:
    """{name: (shape, fan_in or None for embeddings, kind)} of every leaf."""
    m = dims(c)
    d, h, kh, hd, f, L, V = (m["d"], m["heads"], m["kv_heads"], m["hd"],
                             m["ff"], m["layers"], m["vocab"])
    out = {"embed": ((V, d), None, "embed")}
    if not m["tied"]:
        out["lm_head"] = ((d, V), d, "matrix")
    layers = {
        "wq": ((L, d, h, hd), d, "matrix"),
        "wk": ((L, d, kh, hd), d, "matrix"),
        "wv": ((L, d, kh, hd), d, "matrix"),
        "wo": ((L, h, hd, d), h * hd, "matrix"),
        "w_gate": ((L, d, f), d, "matrix"),
        "w_up": ((L, d, f), d, "matrix"),
        "w_down": ((L, f, d), f, "matrix"),
    }
    out["layers"] = layers
    return out


def weight_key(seed: int):
    """A PRNG key that depends on every bit of ``seed`` (``PRNGKey`` alone
    keeps only the low 32 bits of a larger integer)."""
    import jax
    word = int(np.random.SeedSequence(seed & (2**64 - 1)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def make_weights(c: dict, seed: int):
    """Every leaf of the configuration, bfloat16, on the default device,
    from one jitted program. Stacked leaves are drawn layer by layer
    (``lax.map``) so float32 temporaries stay one layer in size."""
    import jax
    import jax.numpy as jnp

    spec = shapes(c)
    flat = [(k, v) for k, v in spec.items() if k != "layers"]
    flat += [(f"layers/{k}", v) for k, v in spec["layers"].items()]

    def draw(key, shape, fan_in, kind):
        z = jax.random.normal(key, shape, jnp.float32)
        if kind == "embed":
            z = 0.02 * z
        else:
            z = z * (fan_in ** -0.5)
        return z.astype(jnp.bfloat16)

    def build(key):
        out = {}
        for i, (name, (shape, fan_in, kind)) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            if name.startswith("layers/"):
                keys = jax.random.split(k, shape[0])
                out[name] = jax.lax.map(
                    lambda kk, s=shape[1:], f=fan_in, t=kind: draw(kk, s, f, t),
                    keys)
            else:
                out[name] = draw(k, shape, fan_in, kind)
        return out

    flat_w = jax.jit(build)(weight_key(seed))
    w = {k: v for k, v in flat_w.items() if not k.startswith("layers/")}
    w["layers"] = {k[len("layers/"):]: v for k, v in flat_w.items()
                   if k.startswith("layers/")}
    return w

