"""Plain reference of the served architectures, and the check that decides
``correct``.

The reference is a straightforward float32 forward pass of the published
architecture (pre-norm decoder with OLMo's LayerNorm, which has no scale
and no bias; RoPE on the first and second half of each head; grouped-query
causal attention; SwiGLU MLP), written here in
``jax.numpy`` with every matrix product at ``Precision.HIGHEST``. It imports
nothing of the program and reads only the benchmark's own weights
(``weights.py``). It runs one layer at a time, and attention in blocks of
queries, so that it fits beside the served model's bf16 weights once the
engine is freed.

``mode="fp8"`` is the control: every matrix product rounds both operands to
float8_e4m3fn with one absmax scale per tensor, the precision below the
bf16 the configurations state.

The check: for each sampled finished request, the reference reads the
prompt followed by the served tokens, and at each served position takes
the gap between its best logit and the logit of the token the engine
served. The widest such gap is compared with the cell's limit. Greedy
decoding in bf16 serves the reference's best token or one within bf16
rounding of it; a wrong cache, position or layer moves the served token
far below the best.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from weights import dims

QUERY_BLOCK = 256


def _fp8(x):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(spec, a, b, mode):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta):
    """x: (R, S, heads, hd) at positions 0..S-1; rotate-half layout."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "mode"))
def _layer(x, layers, i, *, eps, theta, mode):
    """One decoder layer of the reference. x: (R, S, d) float32."""
    w = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
         for k, v in layers.items()}
    R, S, _ = x.shape
    h = _norm(x, eps)
    q = _rope(_mm("rsd,dhk->rshk", h, w["wq"], mode), theta)
    k = _rope(_mm("rsd,dhk->rshk", h, w["wk"], mode), theta)
    v = _mm("rsd,dhk->rshk", h, w["wv"], mode)
    H, KH, hd = q.shape[2], k.shape[2], q.shape[3]
    G = H // KH
    bq = min(QUERY_BLOCK, S)
    kpos = jnp.arange(S)

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * bq, bq, 1)
        qb = qb.reshape(R, bq, KH, G, hd)
        s = _mm("rqkgh,rckh->rkgqc", qb, k, mode) / math.sqrt(hd)
        qpos = b * bq + jnp.arange(bq)
        vis = qpos[:, None] >= kpos[None, :]
        s = jnp.where(vis[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("rkgqc,rckh->rqkgh", p, v, mode).reshape(R, bq, H, hd)

    o = jax.lax.map(block, jnp.arange(S // bq))          # (nb, R, bq, H, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(R, S, H, hd)
    x = x + _mm("rshk,hkd->rsd", o, w["wo"], mode)
    h = _norm(x, eps)
    g = _mm("rsd,df->rsf", h, w["w_gate"], mode)
    u = _mm("rsd,df->rsf", h, w["w_up"], mode)
    return x + _mm("rsf,fd->rsd", jax.nn.silu(g) * u, w["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, pos, head, *, eps, mode):
    """Logits (R, P, V) at positions ``pos`` (R, P) of x (R, S, d)."""
    sel = jnp.take_along_axis(x, pos[..., None], axis=1)
    h = _norm(sel, eps)
    return _mm("rpd,dv->rpv", h, head, mode)


def logits_at(cfg: dict, norm: dict, w: dict, tokens: np.ndarray,
              pos: np.ndarray, mode: str = "f32"):
    """Reference logits (R, P, V) float32 on the device. tokens (R, S)
    int32 with S a multiple of the query block (the tail is padding, which
    causal attention never lets an earlier position see); pos (R, P)."""
    if norm["kind"] != "layernorm_nonparametric":
        raise ValueError(f"the reference has no {norm['kind']!r} norm")
    m = dims(cfg)
    eps = float(norm["eps"])
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for i in range(m["layers"]):
        x = _layer(x, w["layers"], i, eps=eps, theta=m["theta"], mode=mode)
    head = w["embed"].T if m["tied"] else w["lm_head"]
    return _head(x, jnp.asarray(pos), head, eps=eps, mode=mode)


@jax.jit
def _gaps(ref, served, mask):
    """Gap (R, P) of ``served`` tokens below the reference's best logit,
    0 where ``mask`` is False."""
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    return jnp.where(mask, best - got, 0.0)


def pack(samples, rows: int, seq_len: int, positions: int):
    """Lay sampled requests out for the reference. ``samples``: list of
    (prompt int32 array, served int32 array). Returns tokens (rows,
    seq_len), read positions (rows, positions), served tokens and mask,
    both (rows, positions). Unused rows and positions are masked."""
    tokens = np.zeros((rows, seq_len), np.int32)
    pos = np.zeros((rows, positions), np.int32)
    served = np.zeros((rows, positions), np.int32)
    mask = np.zeros((rows, positions), bool)
    for r, (prompt, out) in enumerate(samples):
        n, m = len(prompt), len(out)
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        if len(seq) > seq_len or m > positions or r >= rows:
            raise ValueError(f"sample {r} ({n}+{m} tokens) does not fit "
                             f"{rows}x{seq_len} with {positions} positions")
        tokens[r, :len(seq)] = seq
        pos[r, :m] = np.arange(n - 1, n - 1 + m)
        served[r, :m] = out
        mask[r, :m] = True
    return tokens, pos, served, mask


def served_gap(cfg, norm, w, samples, rows, seq_len, positions,
               control: bool = False) -> dict:
    """The widest gap of the served tokens below the float32 reference's
    best logit. With ``control``, also the widest gap of the tokens that
    the fp8 control would put first at the same positions."""
    tokens, pos, served, mask = pack(samples, rows, seq_len, positions)
    ref = logits_at(cfg, norm, w, tokens, pos, "f32")
    gaps = np.asarray(_gaps(ref, jnp.asarray(served), jnp.asarray(mask)))
    out = {"served_gap": float(gaps.max()),
           "positions": int(mask.sum()),
           "argmax_differs": int(((gaps > 0) & mask).sum())}
    if control:
        low = logits_at(cfg, norm, w, tokens, pos, "fp8")
        first = jnp.argmax(low, -1).astype(jnp.int32)
        del low
        cg = np.asarray(_gaps(ref, first, jnp.asarray(mask)))
        out["control_gap"] = float(cg.max())
        out["control_argmax_differs"] = int(((cg > 0) & mask).sum())
    return out
