"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape x mesh) cell:
  1. PROOF compile — the full model (scan-over-layers, chunked attention):
     jit(step).lower(**input_specs).compile() must succeed on the (16,16)
     single-pod mesh and the (2,16,16) multi-pod mesh; memory_analysis()
     gives the per-device footprint. This is the production artifact.
  2. COST compile — XLA's cost_analysis counts while-loop bodies ONCE
     regardless of trip count, so totals are extracted from a structurally
     identical variant with every loop removed: layers unrolled
     (scan_layers=False) and sequence chunking disabled (single-iteration
     scans are counted correctly). Nothing is executed or allocated; only
     cost_analysis()/HLO text are read. Collective bytes are parsed from
     this unrolled per-device HLO (convention in launch/hlo.py).

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json; the
roofline benchmark (benchmarks/roofline.py) derives the three terms.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape decode_32k --mesh single
  python -m repro.launch.dryrun --all [--mesh both]

A CPU tool by design: it pins JAX to the host platform (512 fake devices)
before JAX is imported, so on a machine with a chip it never takes the TPU.
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from typing import Optional

import jax

from repro.configs import ARCHS, ASSIGNED, SHAPES, applicable_shapes, get_arch, get_shape
from repro.launch import hlo as hlo_mod
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.launch import steps as steps_mod
from repro.launch.steps import step_fn_for
from repro.models import transformer as T

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _mesh_for(name: str):
    return make_production_mesh(multi_pod=(name == "multi"))


def _compile_cell(cfg, shape, mesh, *, want_memory=True, microbatches=1):
    spec = input_specs(cfg, shape, mesh)
    fn = step_fn_for(cfg, spec["kind"], microbatches)
    # donation: train updates (params, opt) in place; decode updates the cache
    donate = {"train": (0, 1), "prefill": (), "decode": (2,)}[spec["kind"]]
    chips = mesh.devices.size
    with mesh:
        lowered = jax.jit(fn, in_shardings=spec["shardings"],
                          donate_argnums=donate).lower(*spec["args"])
        compiled = lowered.compile()
    out = {"kind": spec["kind"]}
    if want_memory:
        ma = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
        }
    # cost_analysis is per-device on the SPMD module -> scale to fleet totals
    ca = compiled.cost_analysis() or {}
    out["cost"] = {"flops": float(ca.get("flops", 0.0)) * chips,
                   "bytes": float(ca.get("bytes accessed", 0.0)) * chips}
    out["hlo_text"] = compiled.as_text()
    return out


def _cost_variant(cfg, kind: str):
    """Loop-free twin: layers unrolled; sequence scans single-iteration.
    The dry-run decode/train/prefill math is unchanged — only loop structure
    differs, so cost_analysis sees every op exactly once."""
    kw = dict(scan_layers=False, remat="none")
    if kind in ("train", "prefill"):
        kw.update(chunk_q=10**9, chunk_kv=10**9, ssm_chunk=10**9)
    return dataclasses.replace(cfg, **kw)


def _apply_overrides(cfg, overrides):
    """--set key=value pairs -> dataclasses.replace (perf-iteration knobs)."""
    if not overrides:
        return cfg
    kw = {}
    for kv in overrides:
        k, v = kv.split("=", 1)
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            v = v.lower() in ("1", "true", "yes")
        elif isinstance(cur, int):
            v = int(v)
        elif isinstance(cur, float):
            v = float(v)
        kw[k] = v
    return dataclasses.replace(cfg, **kw)


def run_cell(arch: str, shape_name: str, mesh_name: str,
             overrides=None, microbatches=None) -> dict:
    from repro.sharding.axes import set_profile
    cfg = _apply_overrides(get_arch(arch), overrides)
    set_profile(cfg.rules_profile)
    shape = get_shape(shape_name)
    mesh = _mesh_for(mesh_name)
    chips = mesh.devices.size
    p = T.superblock_period(cfg)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": int(chips), "n_super": cfg.num_layers // p, "period": p,
           "ok": False}
    t0 = time.time()

    # 1. proof compile (full production model: scanned, chunked, remat,
    #    grad-accumulation per the launcher's memory table)
    mb = (steps_mod.train_microbatches(arch) if shape.kind == "train" else 1)
    if microbatches is not None:
        mb = microbatches
    rec["microbatches"] = mb
    proof = _compile_cell(cfg, shape, mesh, want_memory=True, microbatches=mb)
    rec["kind"] = proof["kind"]
    rec["memory"] = proof["memory"]
    rec["cost_raw"] = proof["cost"]
    rec["proof_compile_s"] = round(time.time() - t0, 2)

    # 2. cost compile (loop-free twin: exact flop/byte/collective totals)
    cv = _compile_cell(_cost_variant(cfg, proof["kind"]), shape, mesh,
                       want_memory=False)
    rec["flops_hlo"] = cv["cost"]["flops"]
    rec["bytes_hlo"] = cv["cost"]["bytes"]
    rec["collective_bytes"] = hlo_mod.collective_bytes(cv["hlo_text"], chips)
    # remat is disabled in the cost twin: recompute overhead is reported
    # separately via the proof module's per-iteration costs in §Roofline.

    rec["total_compile_s"] = round(time.time() - t0, 2)
    rec["ok"] = True
    return rec


def cell_list(mesh_mode: str):
    cells = []
    for arch in ASSIGNED:
        cfg = get_arch(arch)
        for shape in applicable_shapes(cfg):
            for mesh in (("single", "multi") if mesh_mode == "both"
                         else (mesh_mode,)):
                cells.append((arch, shape.name, mesh))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    help="config override key=value (perf iterations)")
    ap.add_argument("--tag", default="",
                    help="artifact filename suffix (perf iterations)")
    ap.add_argument("--microbatches", type=int, default=None)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        # spawn one subprocess per cell: isolates compile memory + failures
        cells = cell_list(args.mesh)
        failures = []
        for arch, shape, mesh in cells:
            path = os.path.join(args.out, f"{arch}__{shape}__{mesh}.json")
            if args.skip_done and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("ok"):
                        print(f"[skip] {arch} {shape} {mesh}")
                        continue
            print(f"[cell] {arch} {shape} {mesh} ...", flush=True)
            t0 = time.time()
            r = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun",
                 "--arch", arch, "--shape", shape, "--mesh", mesh,
                 "--out", args.out],
                capture_output=True, text=True,
                env=dict(os.environ,
                         PYTHONPATH=os.environ.get("PYTHONPATH", "src")))
            dt = time.time() - t0
            ok = r.returncode == 0
            print(f"  -> {'OK' if ok else 'FAIL'} ({dt:.0f}s)", flush=True)
            if not ok:
                failures.append((arch, shape, mesh))
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape, "mesh": mesh,
                               "ok": False,
                               "error": r.stderr[-4000:]}, f, indent=1)
        print(f"done: {len(cells) - len(failures)}/{len(cells)} cells ok")
        if failures:
            print("failures:", failures)
            sys.exit(1)
        return

    assert args.arch and args.shape
    rec = run_cell(args.arch, args.shape, args.mesh, args.overrides,
                   args.microbatches)
    rec["overrides"] = args.overrides
    suffix = f"__{args.tag}" if args.tag else ""
    path = os.path.join(args.out,
                        f"{args.arch}__{args.shape}__{args.mesh}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    mem = rec["memory"]
    per_dev = (mem["argument_bytes"] + mem["temp_bytes"]
               + mem["output_bytes"] - mem["alias_bytes"])
    print(f"[{args.arch} {args.shape} {args.mesh}] kind={rec['kind']} "
          f"chips={rec['chips']}")
    print(f"  memory/device: args={mem['argument_bytes']/2**30:.2f}GiB "
          f"temp={mem['temp_bytes']/2**30:.2f}GiB "
          f"out={mem['output_bytes']/2**30:.2f}GiB "
          f"alias={mem['alias_bytes']/2**30:.2f}GiB "
          f"peak~{per_dev/2**30:.2f}GiB")
    print(f"  flops_hlo={rec['flops_hlo']:.3e} bytes_hlo={rec['bytes_hlo']:.3e} "
          f"collective={rec['collective_bytes'].get('total', 0.0):.3e}B")
    print(f"  compile: proof={rec['proof_compile_s']}s "
          f"total={rec['total_compile_s']}s")


if __name__ == "__main__":
    main()
