"""What every architecture's plain reference shares, and the check that
decides ``correct``.

An architecture's reference (``archs/<name>.py``, ``logits_at``) is a
straightforward float32 forward pass of the published architecture in
``jax.numpy``, with every matrix product through ``mm`` at
``Precision.HIGHEST``. It imports nothing of the program and reads only
the benchmark's own weights (``weights.py``). It runs one layer at a
time, and attention in blocks of queries, so that it fits beside the
served model's bf16 weights once the engine is freed. Shared here: ``mm``,
RoPE on the first and second half of each head (``rope``), and blocked
causal grouped-query attention (``attention``).

``mode="fp8"`` is the control: every matrix product rounds both operands to
float8_e4m3fn with one absmax scale per tensor, the precision below the
bf16 the configurations state.

The check: for each sampled finished request, the reference reads the
prompt followed by the served tokens, and at each served position takes
the gap between its best logit and the logit of the token the engine
served. The widest such gap is compared with the cell's limit. Greedy
decoding in bf16 serves the reference's best token or one within bf16
rounding of it; a wrong cache, position, layer or scale moves the served
token far below the best.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def _fp8(x):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def mm(spec, a, b, mode):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rope(x, theta):
    """x: (R, S, heads, hd) at positions 0..S-1; rotate-half layout."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, mode):
    """Causal grouped-query attention over positions 0..S-1, in blocks of
    ``QUERY_BLOCK`` queries. q (R, S, H, hd); k, v (R, S, KH, hd), with H a
    multiple of KH. Returns (R, S, H, hd)."""
    R, S, H, hd = q.shape
    KH = k.shape[2]
    G = H // KH
    bq = min(QUERY_BLOCK, S)
    kpos = jnp.arange(S)

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * bq, bq, 1)
        qb = qb.reshape(R, bq, KH, G, hd)
        s = mm("rqkgh,rckh->rkgqc", qb, k, mode) / math.sqrt(hd)
        qpos = b * bq + jnp.arange(bq)
        vis = qpos[:, None] >= kpos[None, :]
        s = jnp.where(vis[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("rkgqc,rckh->rqkgh", p, v, mode).reshape(R, bq, H, hd)

    o = jax.lax.map(block, jnp.arange(S // bq))          # (nb, R, bq, H, hd)
    return jnp.moveaxis(o, 0, 1).reshape(R, S, H, hd)


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


def served_gap(logits_at, conf, w, samples, rows, seq_len, positions,
               control: bool = False) -> dict:
    """The widest gap of the served tokens below the float32 reference's
    best logit; ``logits_at`` is the architecture's reference. With
    ``control``, also the widest gap of the tokens that the fp8 control
    would put first at the same positions."""
    tokens, pos, served, mask = pack(samples, rows, seq_len, positions)
    ref = logits_at(conf, w, tokens, pos, "f32")
    gaps = np.asarray(_gaps(ref, jnp.asarray(served), jnp.asarray(mask)))
    out = {"served_gap": float(gaps.max()),
           "positions": int(mask.sum()),
           "argmax_differs": int(((gaps > 0) & mask).sum())}
    if control:
        low = logits_at(conf, w, tokens, pos, "fp8")
        first = jnp.argmax(low, -1).astype(jnp.int32)
        del low
        cg = np.asarray(_gaps(ref, first, jnp.asarray(mask)))
        out["control_gap"] = float(cg.max())
        out["control_argmax_differs"] = int(((cg > 0) & mask).sum())
    return out
