"""Wall-clock spans inside ServeEngine (repro.obs.spans): one step record
per engine step, self times inside the step's wall time, one fetch per
decode step, request lifecycles in order, a bounded ring, compiles and GC
pauses put on the step they landed in, no extra dispatch or host sync with
the profiler on, and the span names in a profiler trace."""
import dataclasses
import gc
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import transformer as T
from repro.models.params import init_params
from repro.obs import spans
from repro.serve import ServeConfig, ServeEngine
from repro.trace import TraceRecorder, poisson_arrivals

KEY = jax.random.PRNGKey(0)
CHILDREN = ("serve.admit", "serve.prefill", "serve.arm", "serve.decode",
            "serve.fetch", "serve.apply")
MODES = [(policy, fuse, superstep) for policy in ("serial", "interleaved")
         for fuse in (False, True) for superstep in (1, 4)]
MODE_IDS = [f"{p}-fuse{int(f)}-k{k}" for p, f, k in MODES]


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("llama3.2-1b").reduced()
    params = init_params(T.param_defs(cfg), KEY)
    return cfg, params


@pytest.fixture(scope="module")
def arrivals(setup):
    cfg, _ = setup
    return poisson_arrivals(0.5, 20, vocab=cfg.vocab_size,
                            prompt_len=(2, 30), max_new=(3, 8), seed=3)


def _engine(cfg, params, policy, fuse, superstep, recorder=None):
    return ServeEngine(cfg, params,
                       ServeConfig(max_slots=4, max_len=64, prefill_chunk=8,
                                   policy=policy, fuse=fuse,
                                   superstep=superstep),
                       recorder=recorder)


def _serve(eng, arrivals):
    """Open-loop serve: each arrival is added once the engine clock reaches
    its step. Returns (engine.step() calls, the engine's Requests)."""
    pending = sorted(arrivals, key=lambda a: a.step)
    reqs, calls, i = [], 0, 0
    while i < len(pending) or eng.queue or any(eng.slot_req):
        while i < len(pending) and pending[i].step <= eng.step_idx:
            eng.add_request(pending[i].prompt, pending[i].max_new)
            reqs.append(eng.queue[-1])
            i += 1
        eng.step()
        calls += 1
    return calls, reqs


@pytest.fixture(scope="module", params=MODES, ids=MODE_IDS)
def served(request, setup, arrivals):
    cfg, params = setup
    rec = TraceRecorder()
    eng = _engine(cfg, params, *request.param, recorder=rec)
    calls, reqs = _serve(eng, arrivals)
    return eng, rec, calls, reqs


def test_one_record_per_step(served):
    eng, _, calls, _ = served
    recs = list(eng.spans.steps)
    assert eng.spans.n_steps == len(recs) == calls
    assert recs[0].step == 0
    for a, b in zip(recs, recs[1:]):
        assert b.step == a.step + a.ticks
    assert recs[-1].step + recs[-1].ticks == eng.step_idx
    assert sum(r.kind is not None for r in recs) == calls


def test_child_self_times_within_step(served):
    eng, _, _, _ = served
    for r in eng.spans.steps:
        assert set(r.self_ns) <= set(CHILDREN)
        assert all(v >= 0 for v in r.self_ns.values())
        assert sum(r.self_ns.values()) <= r.wall_ns


def test_one_fetch_per_decode_step(served):
    eng, rec, _, _ = served
    recs = list(eng.spans.steps)
    decode = [r for r in recs if "serve.decode" in r.self_ns]
    assert decode and all("serve.fetch" in r.self_ns for r in decode)
    assert sum("serve.fetch" in r.self_ns for r in recs) == eng.host_syncs
    # a decode-carrying step is what the scheduler says it is
    assert {r.kind for r in decode} <= {
        "serialized", "decode_only", "superstep", "overlapped", "fused"}


def test_request_lifecycle_in_order(served):
    _, _, _, reqs = served
    assert reqs and all(r.done for r in reqs)
    for r in reqs:
        assert r.t_enqueued <= r.t_admitted <= r.t_first_token <= r.t_done


def test_ring_stays_at_its_bound(setup, arrivals):
    cfg, params = setup
    eng = _engine(cfg, params, "serial", False, 1)
    eng.spans = spans.StepLog(maxlen=8)
    calls, _ = _serve(eng, arrivals)
    assert calls > 8
    assert len(eng.spans.steps) == 8 and eng.spans.n_steps == calls
    assert eng.spans.steps[-1].step + eng.spans.steps[-1].ticks \
        == eng.step_idx


def test_latest_log_outlives_its_engine(setup):
    cfg, params = setup
    eng = _engine(cfg, params, "serial", False, 1)
    eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.run_until_done()
    log = eng.spans
    del eng
    gc.collect()
    assert spans.latest() is log and log in spans.logs()
    assert log.n_steps == 2


@pytest.mark.parametrize("policy,fuse,superstep", MODES, ids=MODE_IDS)
def test_compile_lands_on_its_step(setup, policy, fuse, superstep):
    """A prompt that reaches chunk offsets no earlier prompt reached
    compiles their prefill (or fused) programs inside the steps that
    dispatch them. A config of its own keeps the programs cold."""
    cfg, params = setup
    cold = dataclasses.replace(cfg,
                               name=f"spans-{policy}-{fuse}-{superstep}")
    rec = TraceRecorder()
    eng = _engine(cold, params, policy, fuse, superstep, recorder=rec)
    rng = np.random.default_rng(0)
    for n in (4, 6):                     # one chunk (offset 0) each
        eng.add_request(rng.integers(0, cfg.vocab_size, n),
                        max_new_tokens=2)
    eng.run_until_done()
    offsets = {e["offset"] for e in rec.events if e["type"] == "prefill"}
    warm = eng.spans.n_steps
    eng.add_request(rng.integers(0, cfg.vocab_size, 30),
                    max_new_tokens=2)
    eng.run_until_done()
    recs = list(eng.spans.steps)[warm:]
    new = {e["step"] for e in rec.events
           if e["type"] == "prefill" and e["offset"] not in offsets}
    assert new                           # offsets 8, 16 and 24 are cold
    hit = [r for r in recs if r.step in new]
    assert len(hit) == len(new)
    assert all(r.compiles >= 1 and r.compile_s > 0 for r in hit), hit


def test_forced_gc_lands_on_its_step(setup, arrivals):
    cfg, params = setup
    eng = _engine(cfg, params, "serial", False, 1)
    target = 5
    step = eng.scheduler.step

    def collecting_step(engine):
        if engine.step_idx == target:
            gc.collect()
        return step(engine)

    eng.scheduler.step = collecting_step
    gc.disable()
    try:
        _serve(eng, arrivals)
    finally:
        gc.enable()
    paused = [r.step for r in eng.spans.steps if r.gc_ns > 0]
    assert paused == [target]
    assert all(r.gc_gap_ns == 0 for r in eng.spans.steps)


@pytest.mark.parametrize("policy,fuse,superstep", MODES, ids=MODE_IDS)
def test_profiler_changes_no_dispatch(setup, arrivals, tmp_path, policy,
                                      fuse, superstep):
    cfg, params = setup
    counts = []
    for profile in (False, True):
        eng = _engine(cfg, params, policy, fuse, superstep)
        if profile:
            with jax.profiler.trace(str(tmp_path)):
                _serve(eng, arrivals)
        else:
            _serve(eng, arrivals)
        counts.append((dict(eng.dispatch_counts), eng.host_syncs,
                       eng.spans.n_steps))
    assert counts[0] == counts[1]


def test_profiler_trace_holds_serve_spans(setup, tmp_path):
    from jax.profiler import ProfileData
    cfg, params = setup
    eng = _engine(cfg, params, "serial", False, 1)
    with jax.profiler.trace(str(tmp_path)):
        eng.add_request(np.arange(1, 20), max_new_tokens=3)
        eng.run_until_done()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert {"serve.step", *CHILDREN} <= names
    assert not any(n.startswith("bench.") for n in names)
