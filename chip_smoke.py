"""Chip smoke test: serve full-width olmo-1b on one TPU through ServeEngine.

    python chip_smoke.py

Everything runs in this one process, on ``jax.devices()[0]``; the script
starts no other process. Without a TPU it exits non-zero before any other
phase. Phases, in order:

  device     platform, device kind, device count, JAX and libtpu versions.
  serve      olmo-1b at its published widths (16 layers, d_model 2048, 16
             heads, d_ff 8192, vocab 50304, bf16; random weights from a
             seed), built as ``repro.launch.serve`` builds it, with 8 slots,
             max_len 2048, 256-token prefill chunks and greedy decoding.
             Eight seeded requests of 300-512 prompt tokens and 32 new
             tokens each, four arriving once the first four generate, are
             served twice on one engine: cold (every program compiles) and
             warm. That happens once with the default
             ServeConfig (serial policy, batched prefill, no supersteps) and
             once with the interleaved policy, packed prefill, fused steps
             and supersteps of 4.
  pallas     the prompts' prefill_chunk dispatches with use_pallas=True and
             with use_pallas=False: the kernel program must hold a
             tpu_custom_call, and the cache K/V and the next-token logits
             of the two must agree within KV_TOL.
  reference  a float32 forward_full over prompt + generated tokens. Where
             its top-1 minus top-2 logit margin exceeds MARGIN_TOL, every
             served greedy token must equal its argmax, and the serve runs
             may differ from one another only where it does not.

Lines that start with ``[chip]`` hold wall times measured on the chip: the
host clock around work that ends in ``block_until_ready``. The last line is
one JSON object, ``{"ok": true, "device": {...}}``. A failed check raises,
so the script then ends non-zero without that line.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.metadata
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import init_model  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.params import init_params  # noqa: E402
from repro.serve import ServeConfig, ServeEngine  # noqa: E402
from repro.serve import engine as engine_mod  # noqa: E402

ARCH = "olmo-1b"
SEED = 0
SLOTS, MAX_LEN, CHUNK = 8, 2048, 256
PROMPT_LEN, MAX_NEW = (300, 512), 32
# A served token is held to the float32 argmax only where the reference's
# top-1/top-2 margin exceeds this many logits. bf16 weights are exact in
# float32, so the engine differs from the reference only by bf16 rounding
# of activations: about 0.015 logits per logit at these widths, far below it.
MARGIN_TOL = 0.25
# Pallas vs XLA prefill: the same bf16 computation with another summation
# order in attention. A wrong mask moves later layers' K/V by O(1).
KV_TOL = dict(rtol=5e-2, atol=1e-1)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """The backend compiles JAX reports through ``jax.monitoring``. A
    persistent-cache hit is reported too, with the time it took to read."""

    def __init__(self):
        self.programs = []            # (function name, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kwargs):
        if event == BACKEND_COMPILE:
            self.programs.append((str(kwargs.get("fun_name")), seconds))

    def _event(self, event, **kwargs):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def mark(self):
        return len(self.programs), self.cache_hits

    def since(self, mark) -> str:
        progs = self.programs[mark[0]:]
        return (f"{len(progs)} programs compiled in "
                f"{sum(s for _, s in progs):.3f} s, "
                f"{self.cache_hits - mark[1]} of them read from the "
                f"persistent cache")


def jitted_entries() -> dict:
    """How many jitted serving entries (``serve/engine.py`` ``_jit_*``, one
    per config and static offset/span/length) the process has made."""
    return {name[len("_jit_"):]: fn.cache_info().currsize
            for name, fn in vars(engine_mod).items()
            if name.startswith("_jit_") and fn.cache_info().currsize}


def seeded_prompts(vocab: int, n: int = SLOTS):
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, n)
    return [rng.integers(0, vocab, int(L)).astype(np.int32) for L in lens]


def serve(eng, prompts):
    """The first half of ``prompts`` arrives at once; the second half
    arrives when every request of the first is generating, so its prefill
    overlaps their decode (fused steps where the config fuses). Returns
    the request ids and each request's generated tokens."""
    half = len(prompts) // 2
    rids = [eng.add_request(p, max_new_tokens=MAX_NEW) for p in prompts[:half]]
    results = {}
    for _ in range(MAX_NEW * len(prompts)):
        for rid, tok in eng.step():
            results.setdefault(rid, []).append(tok)
        if len(results) == half:
            break
    else:
        raise AssertionError(f"only {len(results)} of the first {half} "
                             f"requests generated a token")
    rids += [eng.add_request(p, max_new_tokens=MAX_NEW)
             for p in prompts[half:]]
    for rid, toks in eng.run_until_done().items():
        results.setdefault(rid, []).extend(toks)
    return rids, results


def serve_twice(cfg, params, scfg, prompts, log, label):
    """Serve ``prompts`` cold, then warm, on one engine. Returns the greedy
    tokens of each run, one list per request."""
    eng = ServeEngine(cfg, params, scfg)
    runs = []
    for run in ("cold", "warm"):
        mark = log.mark()
        t0 = time.perf_counter()
        rids, results = serve(eng, prompts)
        jax.block_until_ready(eng.cache)
        wall = time.perf_counter() - t0
        toks = [results.get(r, []) for r in rids]
        n = sum(len(t) for t in toks)
        if n != len(prompts) * MAX_NEW:
            raise AssertionError(f"{label} {run}: {n} tokens generated, "
                                 f"expected {len(prompts) * MAX_NEW}")
        print(f"[chip] serve {label} {run}: {wall:.3f} s wall, {n} tokens, "
              f"{n / wall:.1f} tok/s; {log.since(mark)}", flush=True)
        runs.append(toks)
    print(f"[serve {label}] dispatches {eng.dispatch_counts}, "
          f"host syncs {eng.host_syncs}, policy {eng.effective_policy}; "
          f"jitted serving entries so far {jitted_entries()}", flush=True)
    return runs


def kernel_in(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@functools.partial(jax.jit, static_argnames=("rtol", "atol"))
def _close(a, b, *, rtol, atol):
    """(max |a - b|, whether |a - b| <= atol + rtol * |b| everywhere)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    err = jnp.abs(a - b)
    return jnp.max(err), jnp.all(err <= atol + rtol * jnp.abs(b))


def pallas_check(cfg, params, prompts, log):
    """Prefill the prompts chunk by chunk through ``T.prefill_chunk`` with
    use_pallas False and True, then take the next-token logits with
    ``T.decode_step`` (what the engine's first generation step computes).
    Returns the XLA path's logits, one row per prompt."""
    n_chunks = -(-max(len(p) - 1 for p in prompts) // CHUNK)
    tokens = np.zeros((SLOTS, n_chunks * CHUNK), np.int32)
    valid = np.zeros((SLOTS, n_chunks * CHUNK), bool)
    lens = np.zeros(SLOTS, np.int32)
    last = np.zeros((SLOTS, 1), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p) - 1] = p[:-1]
        valid[i, :len(p) - 1] = True
        lens[i], last[i, 0] = len(p) - 1, p[-1]
    decode = jax.jit(functools.partial(T.decode_step, cfg))
    out = {}
    for use_pallas in (False, True):
        cfg_x = dataclasses.replace(cfg, use_pallas=use_pallas)
        cache = init_params(T.cache_defs(cfg, SLOTS, MAX_LEN),
                            jax.random.PRNGKey(0))
        mark = log.mark()
        compiled = []
        for c in range(n_chunks):
            cols = slice(c * CHUNK, (c + 1) * CHUNK)
            args = (params, jnp.asarray(tokens[:, cols]), cache,
                    jnp.asarray(valid[:, cols]))
            prog = jax.jit(functools.partial(
                T.prefill_chunk, cfg_x, offset=c * CHUNK)
            ).lower(*args).compile()
            if kernel_in(prog) != use_pallas:
                raise AssertionError(
                    f"prefill_chunk offset {c * CHUNK} use_pallas="
                    f"{use_pallas}: tpu_custom_call "
                    f"{'missing' if use_pallas else 'present'}")
            compiled.append((prog, cols))
        t0 = time.perf_counter()
        for prog, cols in compiled:
            cache = prog(params, jnp.asarray(tokens[:, cols]), cache,
                         jnp.asarray(valid[:, cols]))
        jax.block_until_ready(cache)
        wall = time.perf_counter() - t0
        logits, _ = decode(params, jnp.asarray(last), cache, jnp.asarray(lens))
        out[use_pallas] = (cache, logits)
        print(f"[chip] prefill use_pallas={use_pallas}: {n_chunks} chunks of "
              f"{SLOTS}x{CHUNK} in {wall:.4f} s wall"
              f"{', tpu_custom_call in every program' if use_pallas else ''}"
              f"; {log.since(mark)}", flush=True)
    (cache_x, logits_x), (cache_p, logits_p) = out[False], out[True]
    checks = [(jax.tree_util.keystr(path), a, b) for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(cache_p)[0],
        jax.tree.leaves(cache_x))]
    checks.append(("next-token logits", logits_p[:len(prompts)],
                   logits_x[:len(prompts)]))
    for name, a, b in checks:
        err, ok = _close(a, b, **KV_TOL)
        print(f"[pallas] {name}: max |pallas - xla| = {float(err):.5f} "
              f"(tolerance {KV_TOL})", flush=True)
        if not bool(ok):
            raise AssertionError(f"Pallas prefill {name} differs from the "
                                 f"XLA path beyond {KV_TOL}")
    return np.asarray(logits_x[:len(prompts)], np.float32)


def reference_check(cfg, params, prompts, runs, engine_logits):
    """Hold every serve run, ``(label, tokens)`` pairs, to a float32
    ``forward_full`` over the first run's prompt + generated tokens."""
    base = np.asarray(runs[0][1], np.int32)                    # (R, MAX_NEW)
    seqs = [np.concatenate([p, g[:-1]]) for p, g in zip(prompts, base)]
    L = -(-max(len(s) for s in seqs) // 64) * 64   # causal: tail pad is inert
    toks = np.zeros((len(seqs), L), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    pos = np.array([[len(p) - 1 + j for j in range(MAX_NEW)] for p in prompts])
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)

    @jax.jit
    def ref(p32, toks, pos):
        logits, _ = T.forward_full(cfg, p32, toks)
        rows = logits[jnp.arange(pos.shape[0])[:, None], pos]  # (R, N, V)
        top2, idx = jax.lax.top_k(rows, 2)
        return rows[:, 0], idx[..., 0], top2[..., 0] - top2[..., 1]

    t0 = time.perf_counter()
    with jax.default_matmul_precision("float32"):
        first, top1, margin = jax.block_until_ready(ref(p32, toks, pos))
    print(f"[chip] reference forward_full (float32, {len(seqs)}x{L} tokens): "
          f"{time.perf_counter() - t0:.3f} s wall, compile included",
          flush=True)
    del p32
    top1, margin = np.asarray(top1), np.asarray(margin)
    sure = margin > MARGIN_TOL
    if not sure.any():
        raise AssertionError(f"no generated position has a reference margin "
                             f"above {MARGIN_TOL}: the check is empty")
    wrong = sure & (base != top1)
    print(f"[reference] {int(sure.sum())} of {sure.size} generated positions "
          f"have a top-1/top-2 margin > {MARGIN_TOL}; served tokens differ "
          f"from the float32 argmax at {int(wrong.sum())} of them; "
          f"{int((base == top1).sum())} of {base.size} equal it overall",
          flush=True)
    if wrong.any():
        raise AssertionError(f"served greedy tokens differ from the float32 "
                             f"argmax at {int(wrong.sum())} positions with "
                             f"margin > {MARGIN_TOL}")
    dlogit = np.abs(engine_logits - np.asarray(first, np.float32)).max()
    print(f"[reference] max |engine - float32| logit at the first generated "
          f"position: {dlogit:.5f}", flush=True)
    for label, toks_run in runs[1:]:
        run = np.asarray(toks_run, np.int32)
        diverged = 0
        for i in range(len(prompts)):
            diff = np.nonzero(run[i] != base[i])[0]
            if diff.size == 0:
                continue
            j = int(diff[0])
            if margin[i, j] > MARGIN_TOL:
                raise AssertionError(
                    f"{label}: request {i} first differs from the first run "
                    f"at token {j}, where the reference margin "
                    f"{margin[i, j]:.4f} > {MARGIN_TOL}")
            diverged += 1
        print(f"[reference] {label}: {len(prompts) - diverged} of "
              f"{len(prompts)} requests token-identical to the first run; "
              f"{diverged} part where the margin is <= {MARGIN_TOL}",
              flush=True)


def run_phases(log):
    t0 = time.perf_counter()
    cfg, params = init_model(ARCH, seed=SEED)
    jax.block_until_ready(params)
    n_weights = sum(x.size for x in jax.tree.leaves(params))
    print(f"[chip] setup: {cfg.name} weights ({n_weights:,}, {cfg.dtype}) made "
          f"from seed {SEED} in {time.perf_counter() - t0:.3f} s", flush=True)
    prompts = seeded_prompts(cfg.vocab_size)
    print(f"[serve] {len(prompts)} requests, prompt lengths "
          f"{[len(p) for p in prompts]}, {MAX_NEW} new tokens each", flush=True)
    default = ServeConfig(max_slots=SLOTS, max_len=MAX_LEN,
                          prefill_chunk=CHUNK)
    configs = {
        "default": default,
        "interleaved": dataclasses.replace(default, policy="interleaved",
                                           pack=True, fuse=True, superstep=4),
    }
    runs = []
    for label, scfg in configs.items():
        cold, warm = serve_twice(cfg, params, scfg, prompts, log, label)
        runs += [(f"{label} cold", cold), (f"{label} warm", warm)]
    engine_logits = pallas_check(cfg, params, prompts, log)
    reference_check(cfg, params, prompts, runs, engine_logits)


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax.devices()[0] is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    print(f"[device] platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}; jax {jax.__version__}, jaxlib "
          f"{_version('jaxlib')}, libtpu {_version('libtpu')}", flush=True)
    print(f"[device] persistent compile cache: {use_compile_cache()}",
          flush=True)
    log = CompileLog()
    t0 = time.perf_counter()
    run_phases(log)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[chip] all phases: {time.perf_counter() - t0:.3f} s wall; "
          f"{log.since((0, 0))}; peak device memory "
          f"{'not reported' if peak is None else f'{peak / 2**30:.2f} GiB'}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
