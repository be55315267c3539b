"""The unified model: interprets every ModelConfig family.

Layer stacking uses *superblocks*: the per-layer (mixer, ffn) kind sequence is
periodic with period p (p=1 for homogeneous stacks, p=8 for Jamba's
1-attention-per-8 + alternating-MoE layout). Parameters for position j in the
superblock are stacked along a leading (num_layers/p) dim and the forward pass
is a single lax.scan over superblocks — HLO stays O(p) regardless of depth,
which is what makes the 61-layer / 1T-param dry-run compile tractable.

Entry points:
  param_defs / cache_defs     — ParamDef trees (init + sharding + dry-run specs)
  forward_full                — train/prefill logits
  loss_fn                     — LM loss (+ MoE aux)
  decode_step                 — one-token generation step against the cache
  decode_and_sample           — decode + sample + terminate (one dispatch)
  decode_superstep            — k decode_and_sample steps under one lax.scan
                                (one dispatch, one host fetch per superstep)
  fused_step[_packed]         — a prefill chunk AND the resident batch's
                                decode_and_sample lowered into ONE program
                                (the overlapped serving step as a single
                                dispatch, not two back-to-back ones)
  encode / prefill_with_cache — serving-side helpers
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as S
from repro.sharding.axes import constrain


# --------------------------------------------------------------------------- #
# superblock structure
# --------------------------------------------------------------------------- #
def superblock_period(cfg: ModelConfig) -> int:
    kinds = list(zip(cfg.layer_kinds(), cfg.ffn_kinds()))
    L_ = len(kinds)
    for p in range(1, L_ + 1):
        if L_ % p == 0 and kinds == kinds[:p] * (L_ // p):
            return p
    return L_


def _position_kinds(cfg: ModelConfig):
    p = superblock_period(cfg)
    return list(zip(cfg.layer_kinds()[:p], cfg.ffn_kinds()[:p]))


# --------------------------------------------------------------------------- #
# parameter defs
# --------------------------------------------------------------------------- #
def _block_defs(cfg: ModelConfig, mixer: str, ffn: str, n_super: int,
                cross: bool = False) -> dict:
    d = {}
    d["norm1"] = L.norm_defs(cfg, stacked=n_super)
    if mixer == "attn":
        d["attn"] = A.attn_defs(cfg, stacked=n_super)
        if cross:
            d["norm_cross"] = L.norm_defs(cfg, stacked=n_super)
            d["cross"] = A.attn_defs(cfg, stacked=n_super, cross=True)
        d["norm2"] = L.norm_defs(cfg, stacked=n_super)
        d["ffn"] = (M.moe_defs(cfg, stacked=n_super) if ffn == "moe"
                    else L.mlp_defs(cfg, stacked=n_super))
    elif mixer == "mamba":
        d["mamba"] = S.mamba_defs(cfg, stacked=n_super)
        d["norm2"] = L.norm_defs(cfg, stacked=n_super)
        d["ffn"] = (M.moe_defs(cfg, stacked=n_super) if ffn == "moe"
                    else L.mlp_defs(cfg, stacked=n_super))
    elif mixer == "rwkv":
        # rwkv: time-mix (mixer) + channel-mix (its own FFN); norm2 separates them
        d["rwkv"] = S.rwkv_defs(cfg, stacked=n_super)
        d["norm2"] = L.norm_defs(cfg, stacked=n_super)
    else:
        raise ValueError(mixer)
    return d


def param_defs(cfg: ModelConfig) -> dict:
    p = superblock_period(cfg)
    n_super = cfg.num_layers // p
    defs: Dict[str, Any] = {"embed": L.embed_defs(cfg)}
    cross = cfg.family == "encdec"
    defs["blocks"] = {
        f"pos{j}": _block_defs(cfg, mixer, ffn, n_super, cross=cross)
        for j, (mixer, ffn) in enumerate(_position_kinds(cfg))
    }
    defs["final_norm"] = L.norm_defs(cfg)
    if cfg.family == "encdec":
        defs["encoder"] = {
            "blocks": {
                "pos0": _block_defs(cfg, "attn", "dense", cfg.encoder_layers)
            },
            "final_norm": L.norm_defs(cfg),
        }
    return defs


def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Decode-time state as a ParamDef tree (zeros init, logical axes drive
    the sharded layout — kv_seq falls back to 'model' for narrow GQA).
    Every leaf stacks the layers first and the slots second; the attention
    K/V is position-major, (layers, slots, max_len, KH, hd), so a token's
    K/V is one contiguous row that a step program writes in place."""
    p = superblock_period(cfg)
    n_super = cfg.num_layers // p
    kh, hd = cfg.num_kv_heads, cfg.head_dim
    out: Dict[str, Any] = {}
    for j, (mixer, _ffn) in enumerate(_position_kinds(cfg)):
        c: Dict[str, Any] = {}
        if mixer == "attn":
            shape = (n_super, batch, max_len, kh, hd)
            axes = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
            kv_dt = "int8" if cfg.kv_dtype == "int8" else cfg.dtype
            c["k"] = ParamDef(shape, axes, "zeros", dtype=kv_dt)
            c["v"] = ParamDef(shape, axes, "zeros", dtype=kv_dt)
            if cfg.kv_dtype == "int8":
                s_shape = (n_super, batch, max_len, kh)
                s_axes = ("layers", "batch", "kv_seq", "kv_heads")
                c["k_scale"] = ParamDef(s_shape, s_axes, "zeros",
                                        dtype="float32")
                c["v_scale"] = ParamDef(s_shape, s_axes, "zeros",
                                        dtype="float32")
            if cfg.family == "encdec":
                xshape = (n_super, batch, kh, cfg.encoder_seq, hd)
                xaxes = ("layers", "batch", "kv_heads", None, "head_dim")
                c["ck"] = ParamDef(xshape, xaxes, "zeros", dtype=cfg.dtype)
                c["cv"] = ParamDef(xshape, xaxes, "zeros", dtype=cfg.dtype)
        elif mixer == "mamba":
            c["conv"] = ParamDef((n_super, batch, cfg.ssm_conv - 1, cfg.d_inner),
                                 ("layers", "batch", None, "d_inner"),
                                 "zeros", dtype=cfg.dtype)
            c["ssm"] = ParamDef((n_super, batch, cfg.d_inner, cfg.ssm_d_state),
                                ("layers", "batch", "d_inner", "d_state"),
                                "zeros", dtype="float32")
        elif mixer == "rwkv":
            c["wkv"] = ParamDef((n_super, batch, cfg.num_heads,
                                 cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                                ("layers", "batch", "rwkv_heads",
                                 "head_dim", None),
                                "zeros", dtype="float32")
            c["shift_tm"] = ParamDef((n_super, batch, cfg.d_model),
                                     ("layers", "batch", "d_model"),
                                     "zeros", dtype=cfg.dtype)
            c["shift_cm"] = ParamDef((n_super, batch, cfg.d_model),
                                     ("layers", "batch", "d_model"),
                                     "zeros", dtype=cfg.dtype)
        out[f"pos{j}"] = c
    return out


# --------------------------------------------------------------------------- #
# layer application
# --------------------------------------------------------------------------- #
def _apply_block_full(cfg: ModelConfig, kind: Tuple[str, str], p: dict,
                      x: jax.Array, positions: jax.Array,
                      enc_kv=None, causal: bool = True):
    """Full-sequence (train/prefill) block. Returns (x, aux_loss)."""
    mixer, ffn = kind
    aux = jnp.zeros((), jnp.float32)
    if mixer == "attn":
        h = L.apply_norm(cfg, p["norm1"], x)
        x = x + A.attention_prefill(cfg, p["attn"], h, positions, causal=causal)
        if enc_kv is not None:
            h = L.apply_norm(cfg, p["norm_cross"], x)
            x = x + A.cross_attention(cfg, p["cross"], h, enc_kv)
        h = L.apply_norm(cfg, p["norm2"], x)
        if ffn == "moe":
            y, aux = M.apply_moe(cfg, p["ffn"], h)
        else:
            y = L.apply_mlp(cfg, p["ffn"], h)
        x = x + y
    elif mixer == "mamba":
        h = L.apply_norm(cfg, p["norm1"], x)
        y, _ = S.mamba_mix(cfg, p["mamba"], h)
        x = x + y
        h = L.apply_norm(cfg, p["norm2"], x)
        if ffn == "moe":
            y, aux = M.apply_moe(cfg, p["ffn"], h)
        else:
            y = L.apply_mlp(cfg, p["ffn"], h)
        x = x + y
    else:  # rwkv
        h = L.apply_norm(cfg, p["norm1"], x)
        y, _ = S.rwkv_time_mix(cfg, p["rwkv"], h)
        x = x + y
        h = L.apply_norm(cfg, p["norm2"], x)
        y, _ = S.rwkv_channel_mix(cfg, p["rwkv"], h)
        x = x + y
    return constrain(x, ("batch", "seq", "d_model")), aux


def _at(leaf: jax.Array, layer) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)


def _put(leaf: jax.Array, layer, value: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_index_in_dim(
        leaf, value.astype(leaf.dtype), layer, 0)


def _apply_block_decode(cfg: ModelConfig, kind: Tuple[str, str], p: dict,
                        x: jax.Array, cache: dict, layer,
                        cur_len: jax.Array):
    """One-token block. x: (B,1,d); ``cache`` is this position's stacked
    state and ``layer`` indexes it. Returns (x, new_cache)."""
    mixer, ffn = kind
    new_cache = dict(cache)
    if mixer == "attn":
        h = L.apply_norm(cfg, p["norm1"], x)
        y, new_cache = A.attention_decode(cfg, p["attn"], h, new_cache,
                                          layer, cur_len)
        x = x + y
        if "ck" in cache:
            h = L.apply_norm(cfg, p["norm_cross"], x)
            x = x + A.cross_attention(cfg, p["cross"], h,
                                      (_at(cache["ck"], layer),
                                       _at(cache["cv"], layer)))
        h = L.apply_norm(cfg, p["norm2"], x)
        if ffn == "moe":
            y, _ = M.apply_moe(cfg, p["ffn"], h)
        else:
            y = L.apply_mlp(cfg, p["ffn"], h)
        x = x + y
    elif mixer == "mamba":
        h = L.apply_norm(cfg, p["norm1"], x)
        y, st = S.mamba_mix(cfg, p["mamba"], h,
                            state={k: _at(cache[k], layer)
                                   for k in ("conv", "ssm")})
        for k in ("conv", "ssm"):
            new_cache[k] = _put(cache[k], layer, st[k])
        x = x + y
        h = L.apply_norm(cfg, p["norm2"], x)
        if ffn == "moe":
            y, _ = M.apply_moe(cfg, p["ffn"], h)
        else:
            y = L.apply_mlp(cfg, p["ffn"], h)
        x = x + y
    else:  # rwkv
        h = L.apply_norm(cfg, p["norm1"], x)
        y, st = S.rwkv_time_mix(cfg, p["rwkv"], h,
                                state={k: _at(cache[k], layer)
                                       for k in ("shift_tm", "wkv")})
        for k in ("shift_tm", "wkv"):
            new_cache[k] = _put(cache[k], layer, st[k])
        x = x + y
        h = L.apply_norm(cfg, p["norm2"], x)
        y, st = S.rwkv_channel_mix(
            cfg, p["rwkv"], h,
            state={"shift_cm": _at(cache["shift_cm"], layer)})
        new_cache["shift_cm"] = _put(cache["shift_cm"], layer, st["shift_cm"])
        x = x + y
    return x, new_cache


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # "full": save only block boundaries


# --------------------------------------------------------------------------- #
# encoder (whisper)
# --------------------------------------------------------------------------- #
def _loop_blocks(cfg: ModelConfig, body, carry, xs):
    """lax.scan over stacked blocks, or an unrolled python loop when
    cfg.scan_layers=False (used by the dry-run cost compiles: XLA's
    cost_analysis counts while bodies once regardless of trip count, so the
    cost-extraction path unrolls; the proof/production path scans)."""
    if cfg.scan_layers:
        return jax.lax.scan(_remat(cfg, body), carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        sl = jax.tree.map(lambda l: l[i], xs)
        carry, y = _remat(cfg, body)(carry, sl)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *ls: jnp.stack(ls), *ys)
    else:
        ys = None
    return carry, ys


def _serve_blocks(cfg: ModelConfig, apply, x, cache: dict, blocks: dict):
    """The serving layer loop: ``apply(kind, p, x, cache_j, layer)`` ->
    (x, cache_j) for each superblock position j of each stacked block. The
    whole cache is loop CARRY (not scanned xs -> ys, which would rebuild a
    fresh stacked copy), so each layer writes only its own rows into the
    one buffer — in place when the caller donates it."""
    kinds = _position_kinds(cfg)
    n = jax.tree.leaves(blocks)[0].shape[0]

    def body(carry, xs):
        x, cache = carry
        blk, layer = xs
        cache = dict(cache)
        for j, kind in enumerate(kinds):
            x, cache[f"pos{j}"] = apply(kind, blk[f"pos{j}"], x,
                                        cache[f"pos{j}"], layer)
        return (x, cache), None

    (x, cache), _ = _loop_blocks(cfg, body, (x, cache),
                                 (blocks, jnp.arange(n)))
    return x, cache


def encode(cfg: ModelConfig, params: dict, frame_embeds: jax.Array) -> jax.Array:
    """frame_embeds: (B, S_enc, d) stub frontend output -> encoder states."""
    x = frame_embeds
    Spos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
    kinds = ("attn", "dense")

    def body(x, blk):
        y, _ = _apply_block_full(cfg, kinds, blk, x, Spos, causal=False)
        return y, None

    x, _ = _loop_blocks(cfg, body, x, params["encoder"]["blocks"]["pos0"])
    return L.apply_norm(cfg, params["encoder"]["final_norm"], x)


# --------------------------------------------------------------------------- #
# full-sequence forward (train / prefill)
# --------------------------------------------------------------------------- #
def forward_full(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
                 patch_embeds: Optional[jax.Array] = None,
                 frame_embeds: Optional[jax.Array] = None,
                 last_only: bool = False):
    """Returns (logits (B,S,V), aux_loss). For vlm, `tokens` covers the text
    part; patch embeddings are prepended so S_total = P + S_text.
    last_only=True emits only the final position's logits (serving prefill:
    a (B, S, vocab) tensor at 32k x 131k vocab would be hundreds of TB)."""
    x = L.embed_tokens(params["embed"], tokens, cfg.d_model)
    if cfg.family == "vlm":
        assert patch_embeds is not None
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
        x = constrain(x, ("batch", "seq", "d_model"))
    B, Stot = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(Stot)[None], (B, Stot))

    enc_kv_per_pos = None
    if cfg.family == "encdec":
        assert frame_embeds is not None
        enc_out = encode(cfg, params, frame_embeds)
    kinds = _position_kinds(cfg)

    def body(carry, blk):
        x, aux = carry
        for j, kind in enumerate(kinds):
            p = blk[f"pos{j}"]
            ekv = None
            if cfg.family == "encdec" and kind[0] == "attn":
                ekv = A.encoder_kv(cfg, p["cross"], enc_out)
            x, a = _apply_block_full(cfg, kind, p, x, positions, enc_kv=ekv)
            aux = aux + a
        return (x, aux), None

    carry0 = (x, jnp.zeros((), jnp.float32))
    (x, aux), _ = _loop_blocks(cfg, body, carry0, params["blocks"])
    if last_only:
        x = x[:, -1:, :]
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.lm_logits(params["embed"], x, cfg.tie_embeddings)
    return logits, aux


def loss_fn(cfg: ModelConfig, params: dict, batch: dict):
    """batch: tokens (B,S[,_]), labels (B,S), optional loss_mask, plus the
    family-specific stub inputs. Returns (loss, metrics)."""
    logits, aux = forward_full(
        cfg, params, batch["tokens"],
        patch_embeds=batch.get("patch_embeds"),
        frame_embeds=batch.get("frame_embeds"))
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if cfg.family == "vlm":
        # logits cover [patches; text]; loss only over text positions
        P = cfg.num_patches
        logits = logits[:, P:, :]
    nll = L.softmax_xent(logits, labels, mask)
    loss = nll + 0.01 * aux
    return loss, {"nll": nll, "aux": aux}


# --------------------------------------------------------------------------- #
# decode step (generation stage)
# --------------------------------------------------------------------------- #
def decode_step(cfg: ModelConfig, params: dict, tokens: jax.Array,
                cache: dict, cur_len: jax.Array):
    """tokens: (B, 1) int32; cur_len: (B,) current context lengths.
    Returns (logits (B, V), new_cache)."""
    x = L.embed_tokens(params["embed"], tokens, cfg.d_model)

    def apply(kind, p, x, c, layer):
        return _apply_block_decode(cfg, kind, p, x, c, layer, cur_len)

    x, new_cache = _serve_blocks(cfg, apply, x, cache, params["blocks"])
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.lm_logits(params["embed"], x, cfg.tie_embeddings)
    return logits[:, 0, :], new_cache


# --------------------------------------------------------------------------- #
# fused generation step: decode + sample + length/termination update — the
# body of the serving engine's single-dispatch decode, factored here so the
# superstep scan and the fused overlapped step can reuse it verbatim
# --------------------------------------------------------------------------- #
def decode_and_sample(cfg: ModelConfig, params: dict, cache: dict,
                      last_tok: jax.Array, lens: jax.Array,
                      active: jax.Array, gen_count: jax.Array,
                      max_new: jax.Array, rng: jax.Array, *,
                      temperature: float, eos_token: Optional[int],
                      max_len: int):
    """One generation step across all slots in ONE program: decode, sample,
    and update per-slot length / termination state. Everything the host
    needs back (sampled token, done flag, new length per slot) is stacked
    into a single (3, B) int32 ``fetch`` array so a dispatch costs exactly
    one device->host transfer. Inactive slots are frozen: their token stays
    ``last_tok`` and their lens/gen_count do not advance — which is also
    what lets the superstep scan keep finished lanes fixed."""
    logits, cache = decode_step(cfg, params, last_tok[:, None], cache, lens)
    rng, sub = jax.random.split(rng)
    if temperature > 0:
        toks = jax.random.categorical(sub, logits / temperature, axis=-1)
    else:
        toks = jnp.argmax(logits, axis=-1)
    toks = jnp.where(active, toks.astype(jnp.int32), last_tok)
    act32 = active.astype(jnp.int32)
    lens = lens + act32
    gen_count = gen_count + act32
    if eos_token is not None:
        eos = toks == eos_token
    else:
        eos = jnp.zeros_like(active)
    done = active & (eos | (gen_count >= max_new)
                     | (lens >= max_len - 1))
    fetch = jnp.stack([toks, done.astype(jnp.int32), lens])
    return fetch, cache, toks, lens, gen_count, rng


def decode_superstep(cfg: ModelConfig, params: dict, cache: dict,
                     last_tok: jax.Array, lens: jax.Array,
                     active: jax.Array, gen_count: jax.Array,
                     max_new: jax.Array, rng: jax.Array, *, k: int,
                     temperature: float, eos_token: Optional[int],
                     max_len: int):
    """k generation steps in ONE dispatch (``lax.scan`` over
    ``decode_and_sample``). The termination mask is carried through the
    scan: a lane that finishes at inner step t is dropped from ``active``
    and frozen for the remaining k-t-1 steps, so per-request tokens are
    identical to k single-step dispatches — the host just resolves one
    (k, 3, B) fetch per superstep instead of one (3, B) fetch per token.
    The rng split sequence matches k single-step dispatches exactly — a
    round with NO live lane keeps the carried rng unsplit, because the
    per-step engine would not have dispatched it at all — so even
    temperature sampling is superstep-invariant (the dead rounds' other
    side effects, K/V writes at frozen cursors, land in rows that
    admission resets before reuse)."""
    def body(carry, _):
        cache, last_tok, lens, active, gen_count, rng = carry
        fetch, cache, last_tok, lens, gen_count, new_rng = decode_and_sample(
            cfg, params, cache, last_tok, lens, active, gen_count,
            max_new, rng, temperature=temperature, eos_token=eos_token,
            max_len=max_len)
        rng = jnp.where(active.any(), new_rng, rng)
        active = active & (fetch[1] == 0)
        return (cache, last_tok, lens, active, gen_count, rng), fetch

    carry0 = (cache, last_tok, lens, active, gen_count, rng)
    (cache, last_tok, lens, _active, gen_count, rng), fetches = \
        jax.lax.scan(body, carry0, None, length=k)
    return fetches, cache, last_tok, lens, gen_count, rng


def fused_step(cfg: ModelConfig, params: dict, cache: dict,
               tokens: jax.Array, tok_valid: jax.Array, row_slot: jax.Array,
               last_tok: jax.Array, lens: jax.Array, active: jax.Array,
               gen_count: jax.Array, max_new: jax.Array, rng: jax.Array, *,
               offset: int, temperature: float, eos_token: Optional[int],
               max_len: int):
    """One FUSED overlapped serving step: the resident batch's decode AND a
    prefill chunk in ONE program — the single-dispatch realization of the
    co-scheduled step the schedulers compose (the simulator scored the
    overlap; this makes it exist on hardware instead of two back-to-back
    dispatches). Order matches the unfused step: the decode reads the
    pre-step cache (its side-effect K/V write for mid-prefill slots lands
    at the parked max_len-1 cursor), then the chunk scatters its K/V — the
    two touch disjoint slots, so numerics are identical by construction."""
    fetch, cache, last_tok, lens, gen_count, rng = decode_and_sample(
        cfg, params, cache, last_tok, lens, active, gen_count, max_new,
        rng, temperature=temperature, eos_token=eos_token, max_len=max_len)
    cache = prefill_chunk(cfg, params, tokens, cache, tok_valid, row_slot,
                          offset=offset)
    return fetch, cache, last_tok, lens, gen_count, rng


def fused_step_packed(cfg: ModelConfig, params: dict, cache: dict,
                      tokens: jax.Array, seg_slot: jax.Array,
                      seg_pos: jax.Array, seg_ids: jax.Array,
                      tok_valid: jax.Array, row_slot: jax.Array,
                      prefix_len: jax.Array, last_tok: jax.Array,
                      lens: jax.Array, active: jax.Array,
                      gen_count: jax.Array, max_new: jax.Array,
                      rng: jax.Array, *, prefix_span: int,
                      temperature: float, eos_token: Optional[int],
                      max_len: int):
    """``fused_step`` with a PACKED prefill chunk (several prompts / a
    continuation tail per lane) riding the decode — one program, one
    dispatch, one fetch."""
    fetch, cache, last_tok, lens, gen_count, rng = decode_and_sample(
        cfg, params, cache, last_tok, lens, active, gen_count, max_new,
        rng, temperature=temperature, eos_token=eos_token, max_len=max_len)
    cache = prefill_chunk_packed(cfg, params, tokens, cache, seg_slot,
                                 seg_pos, seg_ids, tok_valid, row_slot,
                                 prefix_len, prefix_span=prefix_span)
    return fetch, cache, last_tok, lens, gen_count, rng


# --------------------------------------------------------------------------- #
# batched prefill (summarization stage): whole prompt chunks through the
# flash path, K/V written into the slot cache in one dispatch per chunk
# --------------------------------------------------------------------------- #
def supports_batched_prefill(cfg: ModelConfig) -> bool:
    """Attention-mixer stacks only: SSM/RWKV prompts need sequential state
    threading, encdec needs the cross-KV fill — both take the sequential
    path in the serving engine."""
    return (cfg.family != "encdec"
            and all(k == "attn" for k in cfg.layer_kinds()))


def _apply_block_prefill(cfg: ModelConfig, kind: Tuple[str, str], p: dict,
                         x: jax.Array, cache: dict, layer,
                         tok_valid: jax.Array, offset: int,
                         row_slot: jax.Array):
    """Chunk-of-prompt block. x: (R, C, d). Returns (x, new_cache)."""
    mixer, ffn = kind
    if mixer != "attn":
        raise NotImplementedError(
            "batched prefill covers attention mixers only")
    h = L.apply_norm(cfg, p["norm1"], x)
    y, new_cache = A.attention_prefill_cached(cfg, p["attn"], h, cache,
                                              layer, tok_valid, offset,
                                              row_slot)
    x = x + y
    h = L.apply_norm(cfg, p["norm2"], x)
    if ffn == "moe":
        y, _ = M.apply_moe(cfg, p["ffn"], h)
    else:
        y = L.apply_mlp(cfg, p["ffn"], h)
    return x + y, new_cache


def prefill_chunk(cfg: ModelConfig, params: dict, tokens: jax.Array,
                  cache: dict, tok_valid: jax.Array,
                  row_slot: Optional[jax.Array] = None, *, offset: int):
    """One batched-prefill dispatch: tokens (R, C) at global positions
    [offset, offset+C) run through the full stack; every attention layer
    writes its chunk K/V into the cache (writes masked by ``tok_valid``,
    so only admitted slots' rows change). Row j is cache slot
    ``row_slot[j]`` ((R,) int32), so a dispatch carries only the rows being
    prefilled. Returns the new cache.

    Prefill emits no logits: the engine's first generation step feeds the
    last prompt token, so the summarization stage is pure cache fill —
    prefilling an S-token prompt costs ceil(S/C) dispatches instead of S
    sequential decode steps."""
    if row_slot is None:   # four arguments (one row per slot, tools/aot.py)
        row_slot = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = L.embed_tokens(params["embed"], tokens, cfg.d_model)

    def apply(kind, p, x, c, layer):
        return _apply_block_prefill(cfg, kind, p, x, c, layer, tok_valid,
                                    offset, row_slot)

    return _serve_blocks(cfg, apply, x, cache, params["blocks"])[1]


def _apply_block_prefill_packed(cfg: ModelConfig, kind: Tuple[str, str],
                                p: dict, x: jax.Array, cache: dict, layer,
                                seg_slot, seg_pos, seg_ids, tok_valid,
                                row_slot, prefix_len, prefix_span: int):
    """Packed chunk-of-prompts block. x: (B, C, d). Returns (x, new_cache)."""
    mixer, ffn = kind
    if mixer != "attn":
        raise NotImplementedError(
            "packed prefill covers attention mixers only")
    h = L.apply_norm(cfg, p["norm1"], x)
    y, new_cache = A.attention_prefill_packed(
        cfg, p["attn"], h, cache, layer, seg_slot, seg_pos, seg_ids,
        tok_valid, row_slot, prefix_len, prefix_span=prefix_span)
    x = x + y
    h = L.apply_norm(cfg, p["norm2"], x)
    if ffn == "moe":
        y, _ = M.apply_moe(cfg, p["ffn"], h)
    else:
        y = L.apply_mlp(cfg, p["ffn"], h)
    return x + y, new_cache


def prefill_chunk_packed(cfg: ModelConfig, params: dict, tokens: jax.Array,
                         cache: dict, seg_slot: jax.Array,
                         seg_pos: jax.Array, seg_ids: jax.Array,
                         tok_valid: jax.Array, row_slot: jax.Array,
                         prefix_len: jax.Array, *, prefix_span: int):
    """One PACKED batched-prefill dispatch: tokens (B, C) where each row
    carries one or more prompt segments (see the packing planner,
    repro/sched/packing.py). Per-token target (seg_slot, seg_pos) drives
    the K/V scatter; ``seg_ids`` plus per-row (row_slot, prefix_len) drive
    the segment-aware attention mask, so packed prompts only attend their
    own KV prefix. ``prefix_span`` is static — one compiled variant per
    padded prefix length, mirroring the unpacked path's per-offset jit.
    Returns the new cache (packed prefill emits no logits, like
    ``prefill_chunk``)."""
    x = L.embed_tokens(params["embed"], tokens, cfg.d_model)

    def apply(kind, p, x, c, layer):
        return _apply_block_prefill_packed(
            cfg, kind, p, x, c, layer, seg_slot, seg_pos, seg_ids,
            tok_valid, row_slot, prefix_len, prefix_span)

    return _serve_blocks(cfg, apply, x, cache, params["blocks"])[1]


# --------------------------------------------------------------------------- #
# prefill that also fills the cache (serving path; not the dry-run prefill)
# --------------------------------------------------------------------------- #
def prefill_with_cache(cfg: ModelConfig, params: dict, tokens: jax.Array,
                       cache: dict, *, patch_embeds=None, frame_embeds=None):
    """Sequential prefill via decode_step (teacher-forced). Serving uses this
    for short prompts; large-context prefill would use a fused kernel. Returns
    (last_logits, cache, lengths)."""
    B, S = tokens.shape
    if cfg.family == "encdec" and frame_embeds is not None:
        enc_out = encode(cfg, params, frame_embeds)
        kinds = _position_kinds(cfg)
        # fill cross-attention K/V once per layer
        pos_cross = {}
        for j, kind in enumerate(kinds):
            if kind[0] != "attn":
                continue
            blk = params["blocks"][f"pos{j}"]
            def per_layer(cp):
                return A.encoder_kv(cfg, cp, enc_out)
            ck, cv = jax.vmap(per_layer)(blk["cross"])
            pos_cross[f"pos{j}"] = (ck, cv)
        for name, (ck, cv) in pos_cross.items():
            cache[name] = dict(cache[name], ck=ck, cv=cv)

    def step(carry, t):
        cache, lens, _ = carry
        logits, cache = decode_step(cfg, params, tokens[:, t][:, None],
                                    cache, lens)
        return (cache, lens + 1, logits.astype(jnp.float32)), None

    carry0 = (cache, jnp.zeros((B,), jnp.int32), jnp.zeros(
        (B, cfg.vocab_size), jnp.float32))
    (cache, lens, logits), _ = jax.lax.scan(step, carry0, jnp.arange(S))
    return logits, cache, lens
