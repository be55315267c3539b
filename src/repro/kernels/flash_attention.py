"""flash_attention — blocked causal attention for the summarization stage.

Paper mapping: summarization-stage QK^T / softmax / SV run on the Matrix +
Vector units with on-chip staging (Fig. 7a). On TPU, the same structure is
one Pallas kernel: Q block VMEM-resident, K/V streamed block-by-block with
online softmax — scores never touch HBM (the scratch-pad property).

Grid: (B*KH, G, n_q, n_kv); kv innermost, accumulators in VMEM scratch.
GQA: query-head groups G share one KV head (KH kv heads).
Causal masking at block granularity: fully-masked KV blocks are skipped via
pl.when (the grid is static; the body is predicated).

Two masking modes:
  * static ``q_offset`` — the unpacked chunked-prefill case: queries sit at
    global positions [q_offset, q_offset + S), one prompt per row, so the
    causal frontier is a compile-time constant and off-diagonal KV blocks
    are skipped at grid level.
  * dynamic ``segment_info`` — the PACKED chunked-prefill case: one row
    carries several prompts (or the tail of a long one), so positions and
    prompt membership are per-token device arrays. A query attends a key
    iff they share a segment id and the key's position does not exceed the
    query's (causal within the segment); everything else — other prompts
    packed into the same row, padding (segment -1), the row's prefix beyond
    its continuation segment — is masked. Blocks cannot be skipped
    statically, so every KV block runs with the dynamic mask.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, block_q: int, block_kv: int,
            n_kv: int, q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (not causal) or \
        (ki * block_kv <= q_offset + qi * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bkv, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos >= kv_pos, s, NEG_INF)
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


def _kernel_segmented(q_ref, k_ref, v_ref, qpos_ref, qseg_ref, kpos_ref,
                      kseg_ref, o_ref, acc_ref, m_ref, l_ref, *,
                      scale: float, n_kv: int):
    """Packed-prefill body: the mask is fully dynamic (per-token positions
    and segment ids), so every KV block runs — there is no static causal
    frontier to skip on."""
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale             # (bq, d)
    k = k_ref[0].astype(jnp.float32)                        # (bkv, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    q_pos = qpos_ref[0]                                     # (bq, 1)
    q_seg = qseg_ref[0]
    kv_pos = kpos_ref[0]                                    # (1, bkv)
    kv_seg = kseg_ref[0]
    mask = (q_seg == kv_seg) & (q_pos >= kv_pos)
    s = jnp.where(mask, s, NEG_INF)
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


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 256,
                    block_kv: int = 512, q_offset: int = 0,
                    segment_info=None, interpret: bool = False) -> jax.Array:
    """q: (B, H, S, D); k, v: (B, KH, Skv, D) -> (B, H, S, D).

    ``q_offset`` (static) places the queries at global positions
    [q_offset, q_offset + S) against KV positions [0, Skv) — the chunked
    serving-prefill case, where chunk c of a prompt attends causally over
    the cache prefix written by chunks 0..c.

    ``segment_info`` (dynamic) replaces the offset masking for PACKED
    prefill rows: a ``(q_pos, q_seg, kv_pos, kv_seg)`` tuple of int32
    arrays — q_pos/q_seg of shape (B, S), kv_pos/kv_seg of shape (B, Skv).
    A query attends a key iff ``q_seg == kv_seg`` and ``q_pos >= kv_pos``,
    so each packed prompt only sees its own KV prefix; segment id -1 on the
    KV side masks padding unconditionally (give padded queries an id that
    matches nothing, e.g. -2)."""
    B, H, S, D = q.shape
    KH, Skv = k.shape[1], k.shape[2]
    G = H // KH
    bq, bkv = min(block_q, S), min(block_kv, Skv)
    assert S % bq == 0 and Skv % bkv == 0
    assert q_offset == 0 or q_offset + S <= Skv, (q_offset, S, Skv)
    n_q, n_kv = S // bq, Skv // bkv
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B * KH, G, S, D)
    kf = k.reshape(B * KH, Skv, D)
    vf = v.reshape(B * KH, Skv, D)

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, g, qi, ki: (b, g, qi, 0))
    kv_spec = pl.BlockSpec((1, bkv, D), lambda b, g, qi, ki: (b, ki, 0))
    out_spec = pl.BlockSpec((1, 1, bq, D), lambda b, g, qi, ki: (b, g, qi, 0))
    scratch = [
        pltpu.VMEM((bq, D), jnp.float32),
        pltpu.VMEM((bq,), jnp.float32),
        pltpu.VMEM((bq,), jnp.float32),
    ]

    if segment_info is not None:
        q_pos, q_seg, kv_pos, kv_seg = segment_info
        # rows broadcast over kv heads: (B, S) -> (B*KH, S), matching the
        # (B, KH, ...) -> (B*KH, ...) flattening order of q/k/v. Query
        # metadata is laid out as a column (B*KH, S, 1) and KV metadata as a
        # row (B*KH, 1, Skv): the blocks' last two dims then satisfy the
        # TPU's (8, 128) tiling rule and broadcast straight into the
        # (bq, bkv) mask without a relayout.
        def rows(a, n):
            a = jnp.asarray(a, jnp.int32)
            assert a.shape == (B, n), (a.shape, (B, n))
            return jnp.repeat(a, KH, axis=0)
        def col(a, n):
            return rows(a, n)[:, :, None]
        def row(a, n):
            return rows(a, n)[:, None, :]
        qpos_spec = pl.BlockSpec((1, bq, 1), lambda b, g, qi, ki: (b, qi, 0))
        kpos_spec = pl.BlockSpec((1, 1, bkv), lambda b, g, qi, ki: (b, 0, ki))
        kern = functools.partial(_kernel_segmented, scale=scale, n_kv=n_kv)
        out = pl.pallas_call(
            kern,
            grid=(B * KH, G, n_q, n_kv),
            in_specs=[q_spec, kv_spec, kv_spec,
                      qpos_spec, qpos_spec, kpos_spec, kpos_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((B * KH, G, S, D), q.dtype),
            scratch_shapes=scratch,
            interpret=interpret,
        )(qg, kf, vf, col(q_pos, S), col(q_seg, S),
          row(kv_pos, Skv), row(kv_seg, Skv))
        return out.reshape(B, H, S, D)

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             block_q=bq, block_kv=bkv, n_kv=n_kv,
                             q_offset=q_offset)
    out = pl.pallas_call(
        kern,
        grid=(B * KH, G, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((B * KH, G, S, D), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(qg, kf, vf)
    return out.reshape(B, H, S, D)
