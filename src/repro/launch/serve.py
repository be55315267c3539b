"""Serving launcher: batched request replay through the ServeEngine.

  python -m repro.launch.serve --arch llama3.2-1b --smoke --requests 8 \\
      --metrics-out metrics.json --timeline-out trace.json

``--metrics-out`` attaches a live ``repro.obs.MetricsHub`` (zero extra
dispatches / host syncs — it only observes the recorder's event stream)
and writes the SLO report; ``--timeline-out`` writes the Perfetto
trace-event timeline of the serve.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.models.params import init_params
from repro.obs import MetricsHub, engine_events, write_chrome_trace
from repro.serve import ServeConfig, ServeEngine
from repro.trace import TraceRecorder


def init_model(arch: str, *, smoke: bool = False, seed: int = 0):
    """The served model: the registry config (``.reduced()`` for smoke
    runs) and random weights made from ``seed``."""
    cfg = get_arch(arch)
    if smoke:
        cfg = cfg.reduced()
    return cfg, init_params(T.param_defs(cfg), jax.random.PRNGKey(seed))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=0,
                    help="fixed prompt length (0 = random 2..9)")
    ap.add_argument("--prefill-mode", default="batched",
                    choices=["batched", "sequential"])
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--policy", default="serial",
                    choices=["serial", "interleaved", "pim_aware"],
                    help="step-composition policy (repro.sched)")
    ap.add_argument("--pack", action="store_true",
                    help="pack several prompts per prefill chunk row "
                         "(repro/sched/packing.py)")
    ap.add_argument("--prefill-jobs", type=int, default=1,
                    help="concurrent prefill sub-batches (interleaving "
                         "policies)")
    ap.add_argument("--decode-floor", type=int, default=0,
                    help="defer decode below this ready-slot occupancy "
                         "when a prefill chunk fills the step")
    ap.add_argument("--fuse", action="store_true",
                    help="lower an overlapped step (prefill chunk + "
                         "resident-batch decode) into ONE jitted dispatch")
    ap.add_argument("--superstep", type=int, default=1,
                    help="run up to K decode steps per dispatch when no "
                         "prefill work is pending (1 = off)")
    ap.add_argument("--metrics-out", default=None,
                    help="attach a live MetricsHub and write its SLO "
                         "report (JSON) here")
    ap.add_argument("--timeline-out", default=None,
                    help="write a Chrome/Perfetto trace.json of the serve "
                         "here")
    args = ap.parse_args(argv)

    cfg, params = init_model(args.arch, smoke=args.smoke)
    # observability is a pure event-stream consumer: the hub rides the
    # recorder's sink list, so metrics-on serving issues the exact same
    # dispatches and host syncs as metrics-off
    hub = rec = None
    if args.metrics_out or args.timeline_out:
        hub = MetricsHub()
        rec = TraceRecorder(sinks=[hub])
    eng = ServeEngine(cfg, params,
                      ServeConfig(max_slots=args.slots,
                                  max_len=args.max_len,
                                  prefill_mode=args.prefill_mode,
                                  prefill_chunk=args.prefill_chunk,
                                  policy=args.policy, pack=args.pack,
                                  max_prefill_jobs=args.prefill_jobs,
                                  decode_floor=args.decode_floor,
                                  fuse=args.fuse,
                                  superstep=args.superstep),
                      recorder=rec)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = args.prompt_len or int(rng.integers(2, 10))
        eng.add_request(rng.integers(0, cfg.vocab_size, plen),
                        max_new_tokens=args.max_new)
    t0 = time.time()
    results = eng.run_until_done()
    dt = time.time() - t0
    tokens = sum(len(v) for v in results.values())
    print(f"[serve] {len(results)} requests, {tokens} tokens "
          f"in {dt:.2f}s ({tokens/dt:.1f} tok/s)")
    by_phase = {}
    for e in eng.pas_log:
        by_phase.setdefault(e["phase"], []).append(e)
    for phase, entries in by_phase.items():
        gemv = sum(1 for e in entries if e["gemv_path"])
        print(f"[serve] PAS {phase}: {len(entries)} steps, "
              f"{gemv} on the GEMV (PIM-analogue) path")
    print(f"[serve] dispatches: {eng.dispatch_counts['prefill']} prefill "
          f"({eng.effective_prefill_mode}"
          f"{', packed' if args.pack else ''}), "
          f"{eng.dispatch_counts['decode']} decode, "
          f"{eng.dispatch_counts['fused']} fused; "
          f"{eng.host_syncs} host syncs")
    if args.superstep > 1:
        print(f"[serve] supersteps (K={args.superstep}): "
              f"{eng.scheduler.stats['superstep']} dispatches covering "
              f"{eng.superstep_tokens} decode rounds")
    st = eng.prefill_stats
    if st["token_slots"]:
        print(f"[serve] prefill valid-token fraction: "
              f"{st['valid_tokens'] / st['token_slots']:.3f}"
              + (f", decode deferrals: {eng.decode_deferrals}"
                 if eng.decode_deferrals else ""))
    stats = eng.scheduler.stats
    print(f"[serve] policy {eng.effective_policy}: "
          f"{stats['fused']} fused / {stats['overlapped']} overlapped / "
          f"{stats['serialized']} serialized / {stats['decode_only']} "
          f"decode-only steps")
    if rec is not None:
        trace = rec.to_trace()          # finalize: summary reaches the hub
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump(hub.to_dict(), f, indent=2)
            s = hub.summary()
            print(f"[serve] SLO: ttft p50/p99 = "
                  f"{s['ttft_ticks']['p50']:.1f}/{s['ttft_ticks']['p99']:.1f}"
                  f" ticks, tpot p50/p99 = {s['tpot_ticks']['p50']:.1f}/"
                  f"{s['tpot_ticks']['p99']:.1f} ticks")
            print(f"[serve] wrote metrics report -> {args.metrics_out}")
        if args.timeline_out:
            events = engine_events(trace)
            write_chrome_trace(args.timeline_out, events)
            print(f"[serve] wrote {len(events)} trace events -> "
                  f"{args.timeline_out} (load in https://ui.perfetto.dev)")
    return results


if __name__ == "__main__":
    use_compile_cache()
    main()
