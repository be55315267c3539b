"""decode_attention — flash-decode: one query token vs a long KV cache.

Paper mapping: generation-stage QK^T / SV are batched GEMVs against the
cache (mapped to the MU with K/V prefetch pipelining, Fig. 7c). On TPU the
roofline is pure HBM bandwidth over the cache; the kernel streams K/V blocks
HBM->VMEM once with online softmax (the 'PIM internal bandwidth' analogue)
and masks beyond each row's current length.

Grid: (B, KH, n_kv); kv innermost, per-(b,kh) accumulator scratch carries
partial (o, m, l) across cache blocks. The per-row lengths arrive by scalar
prefetch (SMEM): a rank-1 VMEM block of one length would break the TPU's
128-lane tiling rule.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_kv: int, n_kv: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cur_len = len_ref[pl.program_id(0)]
    # skip cache blocks entirely past the valid prefix
    @pl.when(ki * block_kv < cur_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G,bkv)
        pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < cur_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, block_kv: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k, v: (B, KH, S, D); lengths: (B,) int32 -> (B, H, D)."""
    B, H, D = q.shape
    KH, S = k.shape[1], k.shape[2]
    G = H // KH
    bkv = min(block_kv, S)
    assert S % bkv == 0
    n_kv = S // bkv
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, KH, G, D)
    kern = functools.partial(_kernel, scale=scale, block_kv=bkv, n_kv=n_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KH, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ki, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, ki, lens: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, ki, lens: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, ki, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), qg, k, v)
    return out.reshape(B, H, D)
