"""Attention: GQA/MQA/MHA with chunked-flash prefill and flash-decode serving.

Paper mapping (DESIGN.md §2):
  * summarization-stage QKV/attention on the Matrix Unit  -> MXU GEMM path
    (``flash_attention_xla`` — chunked online-softmax so 32k prefill fits;
    kernels/flash_attention.py is the Pallas twin).
  * generation-stage QK^T / SV mapped to the MU, *not* PIM (paper Fig. 7c)
    -> ``decode_attention`` — a batched GEMV against the KV cache. When GQA
    kv_heads cannot shard over the 'model' axis, the cache is
    sequence-sharded and partial softmax results are combined across shards
    (shard_map flash-decode) — the TPU version of the paper's "schedule
    around the shared-memory conflict".
  * head-split/merge with zero data reordering (paper §4.2.1) -> einsum
    layouts keep queries (B, H, S, D) end-to-end; no transposes materialize.
  * the KV cache is position-major: one stacked leaf per attention position
    of the superblock, (layers, slots, S_max, KH, hd), so one token's K/V is
    one contiguous (KH, hd) row. K/V leave the projection in that layout,
    and the step programs carry the stacked leaf through the layer loop and
    write only the rows they fill, at (layer, slot, position) — with the
    leaf donated, XLA updates it in place and no program copies the cache.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef
from repro.models.layers import apply_rope
from repro.sharding.axes import constrain, logical_spec, _current_mesh

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Parameter defs
# --------------------------------------------------------------------------- #
def attn_defs(cfg: ModelConfig, stacked: Optional[int] = None,
              cross: bool = False) -> dict:
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lead = () if stacked is None else (stacked,)
    la = () if stacked is None else ("layers",)
    defs = {
        "wq": ParamDef(lead + (d, h, hd), la + ("d_model", "heads", "head_dim")),
        "wk": ParamDef(lead + (d, kh, hd), la + ("d_model", "kv_heads", "head_dim")),
        "wv": ParamDef(lead + (d, kh, hd), la + ("d_model", "kv_heads", "head_dim")),
        "wo": ParamDef(lead + (h, hd, d), la + ("heads", "head_dim", "d_model")),
    }
    return defs


# --------------------------------------------------------------------------- #
# QKV projection (head-parallel, paper §5.1)
# --------------------------------------------------------------------------- #
def qkv_project(cfg: ModelConfig, p: dict, x: jax.Array,
                positions: Optional[jax.Array], rope: bool = True):
    """q: (B, H, S, hd); k, v position-major (B, S, KH, hd), the layout the
    KV cache stores."""
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = constrain(q, ("batch", "heads", "seq", "head_dim"))
    k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = constrain(v, ("batch", "seq", "kv_heads", "head_dim"))
    if rope and positions is not None:
        q = apply_rope(q, positions[:, None, :], cfg.rope_theta)
        k = apply_rope(k, positions[:, :, None], cfg.rope_theta)
    return q, k, v


def out_project(p: dict, attn_out: jax.Array) -> jax.Array:
    """attn_out: (B, H, S, hd) -> (B, S, d); heads merge with no reorder —
    the contraction replaces the paper's consecutive-address merge trick."""
    out = jnp.einsum("bhsk,hkd->bsd", attn_out, p["wo"])
    return constrain(out, ("batch", "seq", "d_model"))


# --------------------------------------------------------------------------- #
# Chunked flash attention (XLA path) — prefill / train
# --------------------------------------------------------------------------- #
def flash_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool, chunk_q: int, chunk_kv: int,
                        q_offset: int = 0, segment_info=None,
                        return_lse: bool = False, kv_seq_axis: int = 2):
    """Online-softmax blocked attention.

    q: (B, H, Sq, hd); k, v: (B, KH, Skv, hd), or position-major
    (B, Skv, KH, hd) with ``kv_seq_axis=1`` (the cache's layout, read in
    place). GQA via head grouping.
    Scans over query blocks (outer) and KV blocks (inner); O(Sq/cq * Skv/ckv)
    loop nest with O(B*H*cq*ckv) live scores — 32k prefill fits on-chip.

    ``segment_info`` = (q_pos (B,Sq), q_seg (B,Sq), kv_pos (B,Skv),
    kv_seg (B,Skv)) int32 arrays switch the static causal/offset mask to the
    packed-prefill rule: attend iff segments match and q_pos >= kv_pos (the
    XLA twin of the Pallas kernel's ``segment_info`` mode, numerically
    identical structure for CPU tests).
    """
    B, H, Sq, hd = q.shape
    Skv, KH = k.shape[kv_seq_axis], k.shape[3 - kv_seq_axis]
    kv = "bkch" if kv_seq_axis == 2 else "bckh"
    G = H // KH
    scale = 1.0 / math.sqrt(hd)

    def _fit(S, c):
        """Largest divisor of S that is <= c (whisper's 1500-frame encoder
        is not a power of two)."""
        c = min(c, S)
        while S % c:
            c -= 1
        return c

    cq = _fit(Sq, chunk_q)
    ckv = _fit(Skv, chunk_kv)
    nq, nkv = Sq // cq, Skv // ckv

    if segment_info is not None:
        sq_pos, sq_seg, skv_pos, skv_seg = [
            jnp.asarray(a, jnp.int32) for a in segment_info]

    # (B, KH, G, S, hd) grouped views
    qg = q.reshape(B, KH, G, Sq, hd)

    def q_block(carry, qi):
        qb = jax.lax.dynamic_slice_in_dim(qg, qi * cq, cq, axis=3)      # (B,KH,G,cq,hd)
        qb = qb.astype(jnp.float32) * scale
        q_pos = q_offset + qi * cq + jnp.arange(cq)
        if segment_info is not None:
            qp = jax.lax.dynamic_slice_in_dim(sq_pos, qi * cq, cq, 1)   # (B,cq)
            qs = jax.lax.dynamic_slice_in_dim(sq_seg, qi * cq, cq, 1)

        def kv_block(acc, ki):
            o, m, l = acc
            kb = jax.lax.dynamic_slice_in_dim(k, ki * ckv, ckv, kv_seq_axis)
            vb = jax.lax.dynamic_slice_in_dim(v, ki * ckv, ckv, kv_seq_axis)
            s = jnp.einsum(f"bkgqh,{kv}->bkgqc", qb, kb.astype(jnp.float32))
            if segment_info is not None:
                kp = jax.lax.dynamic_slice_in_dim(skv_pos, ki * ckv, ckv, 1)
                ks = jax.lax.dynamic_slice_in_dim(skv_seg, ki * ckv, ckv, 1)
                mask = ((qs[:, :, None] == ks[:, None, :])
                        & (qp[:, :, None] >= kp[:, None, :]))   # (B,cq,ckv)
                s = jnp.where(mask[:, None, None], s, NEG_INF)
            elif causal:
                kv_pos = ki * ckv + jnp.arange(ckv)
                mask = q_pos[:, None] >= kv_pos[None, :]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                f"bkgqc,{kv}->bkgqh", p, vb.astype(jnp.float32))
            return (o_new, m_new, l_new), None

        o0 = jnp.zeros((B, KH, G, cq, hd), jnp.float32)
        m0 = jnp.full((B, KH, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KH, G, cq), jnp.float32)
        # causal: KV blocks past the diagonal contribute nothing; scanning all
        # blocks keeps the HLO static — the Pallas kernel masks at grid level.
        (o, m, l), _ = jax.lax.scan(kv_block, (o0, m0, l0), jnp.arange(nkv))
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        o = o / jnp.maximum(l[..., None], 1e-30)
        return carry, (o.astype(q.dtype), lse)

    _, (blocks, lses) = jax.lax.scan(q_block, None, jnp.arange(nq))
    out = jnp.moveaxis(blocks, 0, 3).reshape(B, KH, G, Sq, hd)
    out = out.reshape(B, H, Sq, hd)
    if return_lse:
        lse = jnp.moveaxis(lses, 0, 3).reshape(B, KH, G, Sq)
        return out, lse
    return out


# --------------------------------------------------------------------------- #
# Flash attention with a flash BACKWARD (custom VJP) — §Perf iteration E
#
# Autodiff-through-the-scans saves every kv-block's (o, m, l) carries for the
# backward pass (GBs per layer at 32k). The custom VJP saves only (q, k, v,
# o, lse) and recomputes score blocks in the backward's own block loop —
# the standard flash-attention backward, O(block^2) transients.
# --------------------------------------------------------------------------- #
@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_fused(q, k, v, causal: bool, chunk_q: int,
                          chunk_kv: int):
    return flash_attention_xla(q, k, v, causal=causal, chunk_q=chunk_q,
                               chunk_kv=chunk_kv)


def _flash_fwd(q, k, v, causal, chunk_q, chunk_kv):
    o, lse = flash_attention_xla(q, k, v, causal=causal, chunk_q=chunk_q,
                                 chunk_kv=chunk_kv, return_lse=True)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, chunk_q, chunk_kv, res, do):
    q, k, v, o, lse = res
    B, H, Sq, hd = q.shape
    KH, Skv = k.shape[1], k.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(hd)

    def _fit(S, c):
        c = min(c, S)
        while S % c:
            c -= 1
        return c

    cq, ckv = _fit(Sq, chunk_q), _fit(Skv, chunk_kv)
    nq, nkv = Sq // cq, Skv // ckv

    qg = q.reshape(B, KH, G, Sq, hd).astype(jnp.float32)
    dog = do.reshape(B, KH, G, Sq, hd).astype(jnp.float32)
    og = o.reshape(B, KH, G, Sq, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lseg = lse  # (B, KH, G, Sq)
    D = jnp.sum(dog * og, axis=-1)                       # (B,KH,G,Sq)

    def q_block(carry, qi):
        dk_acc, dv_acc = carry
        qb = jax.lax.dynamic_slice_in_dim(qg, qi * cq, cq, 3) * scale
        dob = jax.lax.dynamic_slice_in_dim(dog, qi * cq, cq, 3)
        lseb = jax.lax.dynamic_slice_in_dim(lseg, qi * cq, cq, 3)
        Db = jax.lax.dynamic_slice_in_dim(D, qi * cq, cq, 3)
        q_pos = qi * cq + jnp.arange(cq)

        def kv_block(inner, ki):
            dqb, dk_acc, dv_acc = inner
            kb = jax.lax.dynamic_slice_in_dim(kf, ki * ckv, ckv, 2)
            vb = jax.lax.dynamic_slice_in_dim(vf, ki * ckv, ckv, 2)
            s = jnp.einsum("bkgqh,bkch->bkgqc", qb, kb)
            if causal:
                kv_pos = ki * ckv + jnp.arange(ckv)
                mask = q_pos[:, None] >= kv_pos[None, :]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lseb[..., None])             # (B,KH,G,cq,ckv)
            dv_j = jnp.einsum("bkgqc,bkgqh->bkch", p, dob)
            dp = jnp.einsum("bkgqh,bkch->bkgqc", dob, vb)
            ds = p * (dp - Db[..., None])
            dqb = dqb + jnp.einsum("bkgqc,bkch->bkgqh", ds, kb) * scale
            dk_j = jnp.einsum("bkgqc,bkgqh->bkch", ds, qb)  # qb has scale
            dk_acc = jax.lax.dynamic_update_slice_in_dim(
                dk_acc, jax.lax.dynamic_slice_in_dim(
                    dk_acc, ki * ckv, ckv, 2) + dk_j, ki * ckv, 2)
            dv_acc = jax.lax.dynamic_update_slice_in_dim(
                dv_acc, jax.lax.dynamic_slice_in_dim(
                    dv_acc, ki * ckv, ckv, 2) + dv_j, ki * ckv, 2)
            return (dqb, dk_acc, dv_acc), None

        dq0 = jnp.zeros((B, KH, G, cq, hd), jnp.float32)
        (dqb, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_block, (dq0, dk_acc, dv_acc), jnp.arange(nkv))
        return (dk_acc, dv_acc), dqb

    dk0 = jnp.zeros((B, KH, Skv, hd), jnp.float32)
    dv0 = jnp.zeros((B, KH, Skv, hd), jnp.float32)
    (dk, dv), dq_blocks = jax.lax.scan(q_block, (dk0, dv0), jnp.arange(nq))
    dq = jnp.moveaxis(dq_blocks, 0, 3).reshape(B, KH, G, Sq, hd)
    dq = dq.reshape(B, H, Sq, hd).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention_fused.defvjp(_flash_fwd, _flash_bwd)


def attention_prefill(cfg: ModelConfig, p: dict, x: jax.Array,
                      positions: jax.Array, *, causal: bool = True) -> jax.Array:
    q, k, v = qkv_project(cfg, p, x, positions)
    k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    if cfg.flash_vjp:
        o = flash_attention_fused(q, k, v, causal, cfg.chunk_q, cfg.chunk_kv)
    else:
        o = flash_attention_xla(q, k, v, causal=causal,
                                chunk_q=cfg.chunk_q, chunk_kv=cfg.chunk_kv)
    o = constrain(o, ("batch", "heads", "seq", "head_dim"))
    return out_project(p, o)


# --------------------------------------------------------------------------- #
# The stacked KV cache: rows written in place, layers read by index
# --------------------------------------------------------------------------- #
def _write_rows(leaf: jax.Array, layer, slot, pos, rows: jax.Array):
    """Write ``rows`` into a stacked cache leaf at (layer, slot, pos).

    leaf: (layers, slots, S_max, ...); ``slot``/``pos`` broadcast to the
    rows' leading shape and ``rows`` holds one (KH, hd) K/V row (or KH
    int8 scales) per index. A position of S_max or more is dropped, which
    is how padding and non-admitted slots leave the cache untouched. Only
    the named rows are written: a carried, donated leaf stays in place."""
    return leaf.at[layer, slot, pos].set(rows.astype(leaf.dtype),
                                         mode="drop")


def _dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.bfloat16) * scale[..., None].astype(jnp.bfloat16)


def _store_kv(cfg: ModelConfig, cache: dict, put, k_new: jax.Array,
              v_new: jax.Array):
    """Write new K/V rows into the stacked cache with ``put(leaf, rows)``;
    an int8 cache stores them quantized, with one scale per row. Returns
    the new cache and the K/V as the cache now holds them (through the
    int8 round trip), for a chunk that attends its own tokens."""
    if cfg.kv_dtype != "int8":
        cache = dict(cache, k=put(cache["k"], k_new), v=put(cache["v"], v_new))
        return cache, (k_new, v_new)
    kq, ks = _quantize_kv(k_new)
    vq, vs = _quantize_kv(v_new)
    cache = dict(cache, k=put(cache["k"], kq), v=put(cache["v"], vq),
                 k_scale=put(cache["k_scale"], ks),
                 v_scale=put(cache["v_scale"], vs))
    return cache, (_dequantize(kq, ks), _dequantize(vq, vs))


def _layer_kv(cfg: ModelConfig, cache: dict, layer,
              span: Optional[int] = None, slots: Optional[jax.Array] = None):
    """Layer ``layer``'s K/V, position-major (B, span, KH, hd): positions
    [0, span) (all when None) of every slot, or of the ``slots`` rows
    gathered; an int8 cache is dequantized after the gather."""
    def read(name):
        if slots is not None:
            # one gather from the stacked leaf: only the named rows' span
            # is read, never the layer's other slots
            return cache[name][layer, slots, :span]
        x = jax.lax.dynamic_index_in_dim(cache[name], layer, 0, keepdims=False)
        if span is not None:
            x = jax.lax.slice_in_dim(x, 0, span, axis=1)
        return x
    k, v = read("k"), read("v")
    if cfg.kv_dtype == "int8":
        k, v = _dequantize(k, read("k_scale")), _dequantize(v, read("v_scale"))
    return k, v


def _pallas_flash(q, k, v, **kw):
    """The Pallas flash kernel over position-major K/V (it reads them
    head-major, so the attended span is transposed first)."""
    from repro.kernels.flash_attention import flash_attention
    return flash_attention(q, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
                           **kw)


# --------------------------------------------------------------------------- #
# Batched serving prefill (summarization stage): whole prompt chunks through
# the flash path, K/V written into the slot cache in one shot
# --------------------------------------------------------------------------- #
def attention_prefill_cached(cfg: ModelConfig, p: dict, x: jax.Array,
                             cache: dict, layer, tok_valid: jax.Array,
                             offset: int,
                             row_slot: Optional[jax.Array] = None):
    """One prefill chunk against the slot cache. x: (R, C, d) at global
    positions [offset, offset+C); ``cache`` holds this attention position's
    stacked leaves and ``layer`` indexes them. Row j is cache slot
    ``row_slot[j]`` ((R,) int32; None: row j is slot j, R = every slot).
    Writes the chunk's K/V rows into the cache (``tok_valid`` (R, C) False
    drops a write, so other slots' rows are untouched) and attends
    causally over the rows' own cache[:offset+C] via the flash path — one
    dispatch covers R requests' chunks instead of R*C decode steps.

    Returns (out (R, C, d), new_cache). Padding rows (tok_valid False)
    produce garbage outputs — callers discard them."""
    R, C, _ = x.shape
    S = cache["k"].shape[2]
    positions = offset + jnp.broadcast_to(jnp.arange(C)[None], (R, C))
    q, k_new, v_new = qkv_project(cfg, p, x, positions)
    pos = jnp.where(tok_valid, positions, S)
    slot = (jnp.arange(R) if row_slot is None else row_slot)[:, None]
    cache, _ = _store_kv(
        cfg, cache, lambda leaf, r: _write_rows(leaf, layer, slot, pos, r),
        k_new, v_new)
    # attend over the populated prefix only — the span is static (chunk
    # index is baked into the jitted function), so this is a free slice
    span = min(offset + C, S)
    k_att, v_att = _layer_kv(cfg, cache, layer, span, slots=row_slot)
    if cfg.use_pallas:
        # the kernel needs the chunk grid to tile the span exactly; a last
        # chunk that overhangs the cache (max_len not a multiple of the
        # chunk) is refused rather than quietly sent to the XLA twin
        bq, bkv = min(cfg.chunk_q, C), min(cfg.chunk_kv, C)
        if offset + C != span or C % bq or span % bkv:
            raise ValueError(
                f"use_pallas: prefill chunk [{offset}, {offset + C}) does "
                f"not tile the attended span {span} with blocks "
                f"({bq}, {bkv}); make max_len a multiple of the prefill "
                f"chunk and the chunk a multiple of chunk_q/chunk_kv")
        o = _pallas_flash(q, k_att, v_att, causal=True, block_q=bq,
                          block_kv=bkv, q_offset=offset)
    else:
        o = flash_attention_xla(q, k_att, v_att, causal=True,
                                chunk_q=cfg.chunk_q, chunk_kv=cfg.chunk_kv,
                                q_offset=offset, kv_seq_axis=1)
    return out_project(p, o), cache


# --------------------------------------------------------------------------- #
# Packed serving prefill: one chunk ROW carries several prompts (or the tail
# of a long one) — per-token (slot, position) K/V scatter, per-row cache
# prefix gather, segment-masked flash attention
# --------------------------------------------------------------------------- #
def attention_prefill_packed(cfg: ModelConfig, p: dict, x: jax.Array,
                             cache: dict, layer, seg_slot: jax.Array,
                             seg_pos: jax.Array, seg_ids: jax.Array,
                             tok_valid: jax.Array, row_slot: jax.Array,
                             prefix_len: jax.Array, *, prefix_span: int):
    """One PACKED prefill chunk against the slot cache.

    x: (B, C, d) — row b carries one or more prompt segments laid out by the
    packing planner: ``seg_slot``/``seg_pos`` (B, C) give each token's target
    cache row and global position, ``seg_ids`` (B, C) its within-row segment
    id (0 is reserved for the row's continuation segment — the tail of a
    prompt whose earlier chunks are already cached — ids >= 1 are whole
    prompts self-contained in the row, -1 padding). ``row_slot``/
    ``prefix_len`` (B,) name the cache row and true extent of the row's
    continuation prefix; ``prefix_span`` (static, a chunk multiple) is the
    padded slice length the jit specializes on — the packed analogue of the
    unpacked path's static per-chunk ``offset``.

    K/V rows scatter to (layer, seg_slot, seg_pos) — the lane count B is
    decoupled from the cache's slot count, and the planner covers every
    prompt position once, so no two tokens share a cell; invalid tokens
    are dropped. Attention runs over the concatenation [gathered prefix
    rows ; chunk KV] under the segment mask: continuation tokens (segment
    0) attend prefix positions < prefix_len plus their own earlier chunk
    tokens, whole prompts attend only within their segment. Padding rows
    produce garbage outputs — callers discard them."""
    B, C, _ = x.shape
    S = cache["k"].shape[2]
    q, k_new, v_new = qkv_project(cfg, p, x, seg_pos)
    pos = jnp.where(tok_valid, seg_pos, S)                  # S drops
    slot = jnp.where(tok_valid, seg_slot, 0)
    # the chunk attends its own K/V through the same int8 round-trip the
    # cache stores (numerical parity with later chunks reading the cache)
    cache, (k_att_chunk, v_att_chunk) = _store_kv(
        cfg, cache, lambda leaf, r: _write_rows(leaf, layer, slot, pos, r),
        k_new, v_new)

    q_seg = jnp.where(tok_valid, seg_ids, -2)               # pad q matches 0 keys
    kv_seg_chunk = jnp.where(tok_valid, seg_ids, -1)
    if prefix_span > 0:
        # per-row prefix: the continuation segment's cache row, sliced to the
        # static span (>= every row's true prefix; the mask trims to
        # prefix_len so freshly scattered chunk tokens are never re-read)
        span = min(prefix_span, S)
        k_pref, v_pref = _layer_kv(cfg, cache, layer, span, slots=row_slot)
        pref_pos = jnp.broadcast_to(jnp.arange(span)[None], (B, span))
        pref_seg = jnp.where(pref_pos < prefix_len[:, None], 0, -1)
        k_att = jnp.concatenate(
            [k_pref.astype(k_att_chunk.dtype), k_att_chunk], axis=1)
        v_att = jnp.concatenate(
            [v_pref.astype(v_att_chunk.dtype), v_att_chunk], axis=1)
        kv_pos = jnp.concatenate([pref_pos, seg_pos], axis=1)
        kv_seg = jnp.concatenate([pref_seg, kv_seg_chunk], axis=1)
    else:
        k_att, v_att = k_att_chunk, v_att_chunk
        kv_pos, kv_seg = seg_pos, kv_seg_chunk

    seg_info = (seg_pos, q_seg, kv_pos, kv_seg)
    if cfg.use_pallas:
        Skv = k_att.shape[1]
        bq, bkv = min(cfg.chunk_q, C), min(cfg.chunk_kv, Skv)
        if C % bq or Skv % bkv:
            raise ValueError(
                f"use_pallas: packed chunk of {C} tokens over {Skv} keys "
                f"does not tile with blocks ({bq}, {bkv})")
        o = _pallas_flash(q, k_att, v_att, block_q=bq, block_kv=bkv,
                          segment_info=seg_info)
    else:
        o = flash_attention_xla(q, k_att, v_att, causal=True,
                                chunk_q=cfg.chunk_q, chunk_kv=cfg.chunk_kv,
                                segment_info=seg_info, kv_seq_axis=1)
    return out_project(p, o), cache

# --------------------------------------------------------------------------- #
# Cross attention (Whisper decoder)
# --------------------------------------------------------------------------- #
def cross_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                    enc_kv: Tuple[jax.Array, jax.Array]) -> jax.Array:
    """x: (B, S, d); enc_kv: precomputed (k, v) of shape (B, KH, S_enc, hd).
    Encoder memory is short (1500 frames) -> direct einsum."""
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    q = constrain(q, ("batch", "heads", "seq", "head_dim"))
    k, v = enc_kv
    B, H, Sq, hd = q.shape
    KH = k.shape[1]
    qg = q.reshape(B, KH, H // KH, Sq, hd).astype(jnp.float32)
    s = jnp.einsum("bkgqh,bkch->bkgqc", qg / math.sqrt(hd), k.astype(jnp.float32))
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bkch->bkgqh", a, v.astype(jnp.float32))
    o = o.reshape(B, H, Sq, hd).astype(x.dtype)
    return out_project(p, o)


def encoder_kv(cfg: ModelConfig, p: dict, enc_out: jax.Array):
    k = jnp.einsum("bsd,dhk->bhsk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", enc_out, p["wv"])
    return k, v


# --------------------------------------------------------------------------- #
# Decode (generation stage): one token against the KV cache
# --------------------------------------------------------------------------- #
def seq_sharded(num_kv_heads: int, mesh: Optional[Mesh]) -> bool:
    """Layout B: the mesh's 'model' axis does not divide the KV heads, so
    the cache is sharded over its sequence axis instead."""
    if mesh is None or "model" not in mesh.axis_names:
        return False
    ext = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    return ext > 1 and num_kv_heads % ext != 0


def _flash_decode_local(q, k, v, kv_valid):
    """Partial attention over a local KV shard with masking.

    q: (B, KH, G, hd) f32; k/v: (B, S_loc, KH, hd); kv_valid: (B, S_loc) bool.
    Returns (o, m, l): partial output, running max, running sum.
    """
    s = jnp.einsum("bkgh,bckh->bkgc", q, k.astype(jnp.float32))
    s = jnp.where(kv_valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgc,bckh->bkgh", p, v.astype(jnp.float32))
    return o, m, l


def decode_attention(cfg: ModelConfig, q: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, cur_len: jax.Array,
                     mesh: Optional[Mesh] = None) -> jax.Array:
    """q: (B, H, 1, hd). k_cache/v_cache: (B, S_max, KH, hd), valid
    [0, cur_len).

    Two layouts (DESIGN.md §6):
      A. kv_heads shards over 'model'  -> per-device GEMV, no combine.
      B. kv_heads < model extent       -> cache sequence-sharded over 'model';
         shard_map flash-decode with a log-sum-exp combine (psum over model).
    """
    B, H, _, hd = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(hd)
    mesh = mesh or _current_mesh()

    qg = (q.reshape(B, KH, G, hd).astype(jnp.float32)) * scale

    if not seq_sharded(KH, mesh):
        # Layout A — heads sharded (or no TP): plain masked attention.
        valid = jnp.arange(S)[None, :] < cur_len[:, None]              # (B, S)
        o, m, l = _flash_decode_local(qg, k_cache, v_cache, valid)
        out = o / jnp.maximum(l[..., None], 1e-30)
        return out.reshape(B, H, 1, hd).astype(q.dtype)

    # Layout B — sequence-sharded cache + cross-shard softmax combine.
    model_ext = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    batch_axes = logical_spec((B,), ("batch",), mesh)[0]
    cache_spec = logical_spec(k_cache.shape,
                              ("batch", "kv_seq", "kv_heads", "head_dim"),
                              mesh)
    q_spec = P(batch_axes, None, None, None)
    len_spec = P(batch_axes)
    s_loc = S // model_ext

    def body(qg_l, k_l, v_l, cur_l):
        # which global positions live in this shard
        shard = jax.lax.axis_index("model")
        pos = shard * s_loc + jnp.arange(s_loc)
        valid = pos[None, :] < cur_l[:, None]
        o, m, l = _flash_decode_local(qg_l, k_l, v_l, valid)
        # combine across seq shards: global max, then rescaled sums
        m_glob = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_glob)
        l_glob = jax.lax.psum(l * corr, "model")
        o_glob = jax.lax.psum(o * corr[..., None], "model")
        return o_glob / jnp.maximum(l_glob[..., None], 1e-30)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, len_spec),
        out_specs=q_spec,
        check_vma=False,
    )(qg, k_cache, v_cache, cur_len)
    return out.reshape(B, H, 1, hd).astype(q.dtype)


def _write_onehot(leaf: jax.Array, layer, cur_len: jax.Array,
                  rows: jax.Array) -> jax.Array:
    """Layout B's one-token write: select the new row at position cur_len
    over the whole layer — elementwise, so each sequence shard applies it to
    its own positions with no cross-shard traffic, at the cost of rewriting
    the layer."""
    B, S = leaf.shape[1], leaf.shape[2]
    hit = (jnp.arange(S)[None, :] == cur_len[:, None]).reshape(
        (B, S) + (1,) * (leaf.ndim - 3))
    cur = jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    new = jnp.where(hit, rows[:, None].astype(leaf.dtype), cur)
    return jax.lax.dynamic_update_index_in_dim(leaf, new, layer, 0)


def _quantize_kv(x: jax.Array):
    """x: (..., hd) -> (int8 (..., hd), scale (...)): one scale per row."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def attention_decode(cfg: ModelConfig, p: dict, x: jax.Array,
                     cache: dict, layer, cur_len: jax.Array,
                     mesh: Optional[Mesh] = None):
    """One decode step. x: (B, 1, d). ``cache`` holds this attention
    position's stacked leaves, "k"/"v" (layers, B, S_max, KH, hd) (+ the
    int8 cache's "k_scale"/"v_scale" (layers, B, S_max, KH)); ``layer``
    indexes them. The token's K/V row is written at (layer, b, cur_len[b])
    first — one row per slot, or the onehot select of a sequence-sharded
    cache — and the layer is then read back to attend over cur_len + 1.
    Returns (out (B,1,d), new_cache)."""
    positions = cur_len[:, None]                                       # (B, 1)
    q, k_new, v_new = qkv_project(cfg, p, x, positions)
    mesh = mesh or _current_mesh()
    B = x.shape[0]
    if seq_sharded(cfg.num_kv_heads, mesh):
        def put(leaf, rows):
            return _write_onehot(leaf, layer, cur_len, rows)
    else:
        def put(leaf, rows):
            return _write_rows(leaf, layer, jnp.arange(B), cur_len, rows)
    # (quantized on insert for the int8 cache, dequantized at attention
    # time: halves decode HBM traffic — §Perf iteration B2)
    cache, _ = _store_kv(cfg, cache, put, k_new[:, 0], v_new[:, 0])
    k_att, v_att = _layer_kv(cfg, cache, layer)
    o = decode_attention(cfg, q, k_att, v_att, cur_len + 1, mesh)
    return out_project(p, o), cache
