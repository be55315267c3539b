"""§Roofline: derive the three roofline terms per (arch x shape x mesh)
from the dry-run artifacts (launch/dryrun.py JSONs).

    compute term    = HLO_FLOPs / (chips x 197 TFLOP/s)
    memory term     = HLO_bytes / (chips x 819 GB/s)
    collective term = collective_bytes / (chips x 50 GB/s)

plus MODEL_FLOPS (6*N*D train / 2*N_active*D inference) and the
MODEL_FLOPS / HLO_FLOPs usefulness ratio (remat/redundancy detector).

  python -m benchmarks.roofline [--dir artifacts/dryrun] [--markdown]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.configs import get_arch, get_shape
from repro.core import TPU_V5E, TPU_ICI_BW, roofline

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..",
                            "artifacts", "dryrun")


def model_flops(arch: str, shape_name: str) -> float:
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    pc = cfg.param_counts()
    n_active = pc["active"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def _cfg_with_overrides(arch, overrides):
    import dataclasses
    cfg = get_arch(arch)
    for kv in overrides or []:
        k, v = kv.split("=", 1)
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            v = v.lower() in ("1", "true", "yes")
        elif isinstance(cur, (int, float)):
            v = type(cur)(v)
        cfg = dataclasses.replace(cfg, **{k: v})
    return cfg


def memory_bytes_analytic(arch: str, shape_name: str,
                          overrides=None) -> float:
    """Fusion-aware HBM-traffic model (global bytes per step).

    The CPU backend's `bytes accessed` counts every unfused elementwise
    op's operands (XLA:CPU does not fuse like TPU), inflating memory terms
    ~10-40x. This model counts what a TPU actually moves:

      decode:   weights streamed once (+FSDP gather reads), KV cache read,
                one new K/V row per slot and layer written in place.
      prefill:  weights once + per-layer activation traffic at fusion
                granularity + flash-attention KV re-reads (nq passes).
      train:    prefill traffic x3 (fwd + remat recompute + bwd) + grad
                writes + optimizer state read/write.
    """
    cfg = _cfg_with_overrides(arch, overrides)
    shape = get_shape(shape_name)
    bpe = 2
    pc = cfg.param_counts()
    params_b = pc["total"] * bpe
    B = shape.global_batch
    d, f = cfg.d_model, cfg.d_ff

    if shape.kind == "decode":
        S = shape.seq_len
        kv_bpe = 1.25 if cfg.kv_dtype == "int8" else bpe  # +scales
        # KV cache (attention layers only)
        n_attn = sum(1 for k in cfg.layer_kinds() if k == "attn")
        cache_b = 2 * n_attn * B * S * cfg.kv_dim * kv_bpe
        if cfg.family in ("ssm", "hybrid"):
            n_rec = sum(1 for k in cfg.layer_kinds() if k != "attn")
            cache_b += n_rec * B * (cfg.d_inner * cfg.ssm_d_state * 4
                                    if cfg.family == "hybrid"
                                    else cfg.num_heads * cfg.rwkv_head_dim**2
                                    * 4)
        weights = params_b
        if cfg.is_moe and not cfg.fsdp_params:
            # only routed experts are touched
            active_frac = min(1.0, B * cfg.experts_per_token
                              / max(1, cfg.num_experts))
            expert_b = (cfg.num_layers * cfg.num_experts * 3 * d * f * bpe
                        if cfg.moe_every == 1 else 0)
            weights = params_b - expert_b * (1 - active_frac)
        if cfg.fsdp_params and cfg.moe_impl != "ep":
            weights *= 2.0         # resident read + gathered write
        kv_write = 2 * n_attn * B * cfg.kv_dim * kv_bpe
        return weights + cache_b + kv_write

    S = shape.seq_len
    tok = B * S
    # per-layer fused activation traffic
    per_layer = 0.0
    for kind, fk in zip(cfg.layer_kinds(), cfg.ffn_kinds()):
        if kind == "attn":
            per_layer += tok * (8 * d + 2 * cfg.q_dim + 2 * cfg.kv_dim) * bpe
            # flash: K/V re-read once per query block, per attention layer
            nq = max(1, S // cfg.chunk_q)
            per_layer += nq * 2 * B * S * cfg.kv_dim * bpe
        elif kind == "mamba":
            per_layer += tok * (6 * d + 6 * cfg.d_inner) * bpe
        else:  # rwkv
            per_layer += tok * (10 * d + 6 * cfg.num_heads
                                * cfg.rwkv_head_dim) * bpe
        if fk == "moe":
            Tg = tok  # groups split it, totals unchanged
            C_total = Tg * cfg.experts_per_token * cfg.capacity_factor
            per_layer += C_total * (2 * d + 2 * f) * bpe
        elif kind == "attn" or kind == "mamba":
            per_layer += tok * 3 * f * bpe
    act = per_layer  # summed over layers already via the loop
    # encoder (whisper): bidirectional attention over the frame stub
    if cfg.encoder_layers:
        etok = B * cfg.encoder_seq
        act += cfg.encoder_layers * etok * (8 * d + 2 * cfg.q_dim
                                            + 2 * cfg.kv_dim + 3 * f) * bpe
        # decoder cross-attention reads encoder K/V per layer
        act += cfg.num_layers * 2 * etok * cfg.kv_dim * bpe
    # logits + loss
    act += B * (S if shape.kind == "train" else 1) * cfg.vocab_size * bpe
    if shape.kind == "prefill":
        return params_b + act
    # train: fwd + remat recompute + bwd activations; params read fwd+bwd,
    # grads written, optimizer (factored) negligible
    return 3 * params_b + 3 * act


def load_records(art_dir: str):
    recs = []
    for path in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        parts = os.path.basename(path)[:-5].split("__")
        rec["tag"] = parts[3] if len(parts) > 3 else ""
        recs.append(rec)
    return recs


def analyze(rec: dict) -> dict:
    chips = rec["chips"]
    mem_bytes = memory_bytes_analytic(rec["arch"], rec["shape"],
                                      rec.get("overrides"))
    terms = roofline(rec["flops_hlo"], mem_bytes,
                     rec["collective_bytes"].get("total", 0.0), chips)
    mf = model_flops(rec["arch"], rec["shape"])
    mem = rec.get("memory", {})
    peak = (mem.get("argument_bytes", 0) + mem.get("temp_bytes", 0)
            + mem.get("output_bytes", 0) - mem.get("alias_bytes", 0))
    out = {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "tag": rec.get("tag", ""), "chips": chips,
        "compute_s": terms.compute_s,
        "memory_s": terms.memory_s,
        "collective_s": terms.collective_s,
        # the unfused CPU-backend byte count, reported as the upper bound
        "memory_s_unfused": rec["bytes_hlo"] / (chips * TPU_V5E.ext_bw),
        "dominant": terms.dominant,
        "bound_s": terms.bound_s,
        "model_flops": mf,
        "useful_ratio": mf / rec["flops_hlo"] if rec["flops_hlo"] else 0.0,
        # roofline fraction: ideal compute time at peak / achievable bound
        "roofline_frac": (mf / (chips * TPU_V5E.mu_flops)) / terms.bound_s
        if terms.bound_s else 0.0,
        "peak_gib": peak / 2**30,
        "fits_16g": peak <= 16 * 2**30,
    }
    return out


def suggestion(row: dict) -> str:
    if row["dominant"] == "collective":
        return "overlap/shrink collectives (async, int8, 2D layouts)"
    if row["dominant"] == "memory":
        return "cut HBM traffic (KV scatter-update, fusion, bf16 paths)"
    return "raise MXU utilization (larger tiles, fewer pad passes)"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default=ARTIFACT_DIR)
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--mesh", default=None)
    args = ap.parse_args()
    rows = []
    for rec in load_records(args.dir):
        if not rec.get("ok"):
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "mesh": rec["mesh"], "error": True})
            continue
        if args.mesh and rec["mesh"] != args.mesh:
            continue
        rows.append(analyze(rec))
    if args.markdown:
        print("| arch | shape | mesh | variant | compute s | memory s | "
              "coll s | dominant | MODEL/HLO | roofline frac | peak GiB | "
              "fits |")
        print("|---|---|---|---|---|---|---|---|---|---|---|---|")
        for r in rows:
            if r.get("error"):
                print(f"| {r['arch']} | {r['shape']} | {r['mesh']} | | "
                      f"FAILED | | | | | | | |")
                continue
            print(f"| {r['arch']} | {r['shape']} | {r['mesh']} "
                  f"| {r.get('tag','') or 'baseline'} "
                  f"| {r['compute_s']:.3e} | {r['memory_s']:.3e} "
                  f"| {r['collective_s']:.3e} | {r['dominant']} "
                  f"| {r['useful_ratio']:.2f} | {r['roofline_frac']:.3f} "
                  f"| {r['peak_gib']:.1f} | "
                  f"{'y' if r['fits_16g'] else 'N'} |")
    else:
        print("name,us_per_call,derived")
        for r in rows:
            if r.get("error"):
                print(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']},0,ERROR")
                continue
            print(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']},"
                  f"{r['bound_s']*1e6:.1f},"
                  f"dom={r['dominant']};frac={r['roofline_frac']:.3f};"
                  f"useful={r['useful_ratio']:.2f};"
                  f"fix={suggestion(r)}")
    return rows


if __name__ == "__main__":
    main()
