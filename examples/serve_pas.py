"""End-to-end serving with live PAS decisions (the paper's core idea).

Serves batched requests through the continuous-batching engine while the
Algorithm-1 twin routes every step's FC work between the GEMM (MXU) path
and the streaming-GEMV (PIM-analogue) path, and prints the decisions.

    PYTHONPATH=src python examples/serve_pas.py
"""
import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_arch
from repro.core import FCConfig, IANUS_HW, TPU_V5E, route_fc_tpu
from repro.core.cost_model import pim_fc_time, pipelined_mu_time
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve import ServeConfig, ServeEngine
from repro.trace import TraceRecorder


def main():
    cfg = get_arch("llama3.2-1b").reduced()
    params = init_params(T.param_defs(cfg), jax.random.PRNGKey(0))
    rec = TraceRecorder()
    eng = ServeEngine(cfg, params, ServeConfig(max_slots=4, max_len=96,
                                               prefill_chunk=16),
                      recorder=rec)

    rng = np.random.default_rng(0)
    for i in range(10):
        eng.add_request(rng.integers(0, cfg.vocab_size, rng.integers(2, 32)),
                        max_new_tokens=12)
    results = eng.run_until_done()
    print(f"served {len(results)} requests, "
          f"{sum(map(len, results.values()))} tokens")
    print(f"dispatches: {eng.dispatch_counts['prefill']} batched-prefill, "
          f"{eng.dispatch_counts['decode']} decode")
    # the paper's two phases, live from the route each recorded dispatch
    # carries: summarization (batched prompt chunks) routes GEMM,
    # generation (small active batch) routes GEMV — Algorithm 1 picks per
    # phase, not per model
    routes = [e["route"] for e in rec.events
              if e["type"] in ("prefill", "decode")]
    print(f"{'phase':>14} {'tokens':>7} {'ffn_route':>10} {'gemv_path':>10}")
    for e in routes[:8]:
        print(f"{e['phase']:>14} {e['tokens']:>7} {e['ffn_route']:>10} "
              f"{str(e['gemv_path']):>10}")
    gen = [e for e in routes if e["phase"] == "generation"]
    gemv = sum(e["gemv_path"] for e in gen)
    print(f"...\nPAS: {gemv}/{len(gen)} generation steps took the "
          f"GEMV (PIM-analogue) path\n")

    # the Algorithm-1 crossover, on real model dims (llama3.2-1b FFN)
    full = get_arch("llama3.2-1b")
    fc = FCConfig(full.d_model, full.d_ff)
    print(f"Algorithm 1 crossover for the {full.name} FFN "
          f"({fc.d_in}x{fc.d_out}), TPU v5e engine model:")
    print(f"{'tokens':>8} {'gemm_us':>10} {'gemv_us':>10} {'route':>6}")
    for n in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512):
        g = pipelined_mu_time(TPU_V5E, n, fc) * 1e6
        v = pim_fc_time(TPU_V5E, n, fc) * 1e6
        print(f"{n:>8} {g:>10.1f} {v:>10.1f} "
              f"{route_fc_tpu(n, fc.d_in, fc.d_out):>6}")
    print("\n(IANUS engine model for comparison:)")
    for n in (1, 8, 16, 128):
        g = pipelined_mu_time(IANUS_HW, n, fc) * 1e6
        v = pim_fc_time(IANUS_HW, n, fc) * 1e6
        win = "PIM" if v < g else "MU"
        print(f"{n:>8} mu={g:>9.1f}us pim={v:>9.1f}us -> {win}")


if __name__ == "__main__":
    main()
