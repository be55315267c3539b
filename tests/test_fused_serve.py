"""Fused serving steps: single-dispatch overlapped prefill+decode,
multi-step decode supersteps, schema v4, span-aware replay, per-lane
prefix-span segregation, and real-length workloads."""
import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.pas import PASPolicy, merge_streams
from repro.models import transformer as T
from repro.models.params import init_params
from repro.sched import choose_superstep, plan_packed_job
from repro.serve import Request, ServeConfig, ServeEngine
from repro.sim import SimConfig, Simulator, graphs
from repro.trace import (Trace, TraceRecorder, TraceReplayer, drive,
                         group_dispatch_spans, lengths_from_file,
                         poisson_arrivals, trace_to_commands)

KEY = jax.random.PRNGKey(0)
POLICIES = ("serial", "interleaved", "pim_aware")
FULL_DIMS = (2048, 8192)          # llama3.2-1b (pim_aware mapping dims)
DATA_DIR = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "data")


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("llama3.2-1b").reduced()
    params = init_params(T.param_defs(cfg), KEY)
    return cfg, params


def _scfg(policy, **kw):
    base = dict(max_slots=4, max_len=64, prefill_chunk=8, policy=policy,
                map_dims=FULL_DIMS)
    base.update(kw)
    return ServeConfig(**base)


def _serve(cfg, params, policy, arrivals, **kw):
    rec = TraceRecorder()
    eng = ServeEngine(cfg, params, _scfg(policy, **kw), recorder=rec)
    results = drive(eng, arrivals)
    return eng, rec, results


@pytest.fixture(scope="module")
def arrivals(setup):
    cfg, _ = setup
    return poisson_arrivals(0.5, 24, vocab=cfg.vocab_size,
                            prompt_len=(2, 40), max_new=(3, 8), seed=1)


@pytest.fixture(scope="module")
def baseline(setup, arrivals):
    cfg, params = setup
    return _serve(cfg, params, "serial", arrivals)


@pytest.fixture(scope="module")
def fused_superstep_serve(setup, arrivals):
    """One mixed serve with BOTH features on (interleaved + pack + fuse +
    superstep) — the trace mixes fused, superstep and plain steps."""
    cfg, params = setup
    return _serve(cfg, params, "interleaved", arrivals, pack=True,
                  fuse=True, superstep=4)


# --------------------------------------------------------------------------- #
# acceptance: numerics are invariant to how steps are dispatched
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("policy", POLICIES)
def test_fused_matches_unfused(setup, arrivals, baseline, policy):
    """Greedy tokens identical fused-vs-unfused overlapped steps under
    every policy (serial never overlaps; it pins the reference)."""
    cfg, params = setup
    eng, _rec, res = _serve(cfg, params, policy, arrivals, fuse=True)
    assert res == baseline[2]
    if policy != "serial":
        assert eng.scheduler.stats["fused"] > 0
        assert eng.dispatch_counts["fused"] > 0
    else:
        assert eng.dispatch_counts["fused"] == 0


@pytest.mark.parametrize("policy", POLICIES)
def test_superstep_matches_single_step(setup, arrivals, baseline, policy):
    """Greedy tokens identical across superstep in {1, 4} under every
    policy; supersteps really fire on the pure-decode tail."""
    cfg, params = setup
    eng, _rec, res = _serve(cfg, params, policy, arrivals, superstep=4)
    assert res == baseline[2]
    assert eng.scheduler.stats["superstep"] > 0
    assert eng.superstep_tokens > 0


def test_fused_superstep_packed_matches(fused_superstep_serve, baseline):
    """Everything at once (pack + fuse + superstep) still emits the
    reference tokens."""
    assert fused_superstep_serve[2] == baseline[2]


@pytest.mark.parametrize("policy", ("serial", "pim_aware"))
def test_packed_superstep_matches_serial(setup, arrivals, baseline, policy):
    """pack + fuse + superstep 4 under the policies the mixed serve above
    does not run: every donated step program leaves the reference tokens."""
    cfg, params = setup
    _eng, _rec, res = _serve(cfg, params, policy, arrivals, pack=True,
                             fuse=True, superstep=4)
    assert res == baseline[2]


def _backend_donates() -> bool:
    x = jnp.zeros((4,))
    jax.jit(lambda a: a + 1, donate_argnums=0)(x)
    return x.is_deleted()


@pytest.mark.parametrize("arch,policy,kw", [
    ("llama3.2-1b", "serial", {}),
    ("llama3.2-1b", "serial", {"pack": True, "superstep": 4}),
    ("llama3.2-1b", "interleaved", {"fuse": True}),
    ("llama3.2-1b", "interleaved", {"pack": True, "fuse": True}),
    ("rwkv6-7b", "serial", {}),            # the sequential prefill path
])
def test_engine_steps_donate_the_cache(arch, policy, kw):
    """Every step that dispatches hands the cache it held to the program:
    the arrays the engine held before the step are deleted after it, so no
    step kept a second copy of the cache alive."""
    if not _backend_donates():
        pytest.skip("this backend ignores buffer donation")
    cfg = get_arch(arch).reduced()
    params = init_params(T.param_defs(cfg), KEY)
    eng = ServeEngine(cfg, params, _scfg(policy, **kw))
    rng = np.random.default_rng(5)
    for n in (9, 20, 3):
        eng.add_request(rng.integers(0, cfg.vocab_size, n), 6)
    steps = 0
    while eng.queue or any(r is not None for r in eng.slot_req):
        held = jax.tree.leaves(eng.cache)
        counts = dict(eng.dispatch_counts)
        eng.step()
        if eng.dispatch_counts != counts:
            assert all(leaf.is_deleted() for leaf in held), steps
            steps += 1
    assert steps > 0


def test_superstep_rng_freezes_on_dead_rounds(setup):
    """The scan must not consume rng splits on rounds with no live lane
    (the per-step engine would never have dispatched them): after the only
    lane dies at inner round 1 of k=4, the returned rng is exactly one
    split deep."""
    cfg, params = setup
    from repro.models.params import init_params as _init
    B, L = 2, 16
    cache = _init(T.cache_defs(cfg, B, L), KEY)
    lens = jnp.full((B,), L - 2, jnp.int32)       # dies at the cap after 1
    active = jnp.asarray([True, False])
    rng0 = jax.random.PRNGKey(42)
    fetches, _c, _t, _l, _g, rng_k = T.decode_superstep(
        cfg, params, cache, jnp.zeros((B,), jnp.int32), lens, active,
        jnp.zeros((B,), jnp.int32), jnp.full((B,), 8, jnp.int32), rng0,
        k=4, temperature=0.7, eos_token=None, max_len=L)
    assert fetches.shape[0] == 4
    assert bool(fetches[0, 1, 0])                 # lane 0 done at round 1
    assert jnp.array_equal(rng_k, jax.random.split(rng0)[0])


def test_superstep_invariant_past_early_termination(setup):
    """Temperature sampling is superstep-invariant even when lanes
    terminate early via the max_len cap: a later-admitted request must
    sample from the identical rng stream under superstep in {1, 4}."""
    cfg, params = setup
    rng = np.random.default_rng(9)
    # lens starts at 5 (prompt[:-1] cached); the len cap (max_len-1 = 7)
    # kills the lane at inner round 2 of a k=4 superstep, leaving two dead
    # tail rounds
    first = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    second = rng.integers(0, cfg.vocab_size, 3).astype(np.int32)
    res = {}
    for k in (1, 4):
        eng = ServeEngine(cfg, params,
                          _scfg("serial", max_len=8, superstep=k,
                                temperature=0.8))
        eng.add_request(first, max_new_tokens=16)   # dies at the len cap
        out = {}
        for _ in range(12):
            for rid, tok in eng.step():
                out.setdefault(rid, []).append(tok)
        rid2 = eng.add_request(second, max_new_tokens=3)
        for _ in range(30):
            if not eng.queue and all(r is None for r in eng.slot_req):
                break
            for rid, tok in eng.step():
                out.setdefault(rid, []).append(tok)
        res[k] = out
        assert rid2 in out and len(out[rid2]) == 3
    assert res[1] == res[4]


def test_int8_cache_fused_superstep(setup):
    """The fused program and the superstep scan honour the int8 KV cache
    round-trip."""
    cfg, _ = setup
    cfg8 = dataclasses.replace(cfg, kv_dtype="int8")
    params = init_params(T.param_defs(cfg8), KEY)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg8.vocab_size, p).astype(np.int32)
               for p in (5, 17, 2, 11)]
    res = {}
    for key, kw in {
        "base": dict(),
        "fused": dict(policy="interleaved", fuse=True),
        "superstep": dict(superstep=4),
        "both": dict(policy="interleaved", fuse=True, superstep=4,
                     pack=True),
    }.items():
        eng = ServeEngine(cfg8, params, _scfg(kw.pop("policy", "serial"),
                                              **kw))
        for p in prompts:
            eng.add_request(p, max_new_tokens=4)
        res[key] = eng.run_until_done()
    assert res["fused"] == res["base"]
    assert res["superstep"] == res["base"]
    assert res["both"] == res["base"]


# --------------------------------------------------------------------------- #
# dispatch accounting: one dispatch per fused step, 1/k per superstep token
# --------------------------------------------------------------------------- #
def test_fused_step_is_single_dispatch(fused_superstep_serve):
    """A fused overlapped step is ONE dispatch: the engine counts it in
    neither the prefill nor the decode bucket, and the trace records the
    pair as two events of one dispatch (same step, both fused)."""
    eng, rec, _res = fused_superstep_serve
    tr = rec.to_trace()
    fused_pf = [e for e in tr.of_type("prefill") if e["fused"]]
    fused_dec = [e for e in tr.of_type("decode") if e["fused"]]
    assert len(fused_pf) == len(fused_dec) == eng.dispatch_counts["fused"]
    assert eng.scheduler.stats["fused"] == eng.dispatch_counts["fused"] > 0
    dec_by_step = {e["step"]: e for e in fused_dec}
    for pf in fused_pf:
        dec = dec_by_step[pf["step"]]      # the pair shares its step...
        assert pf["overlap"] and dec["overlap"]
        # ...and no third dispatch shares it
        assert sum(e["step"] == pf["step"] for e in tr.schedulable) == 2
    # chunk work and decode work both happened, each once per fused step
    total_chunks = (eng.dispatch_counts["prefill"]
                    + eng.dispatch_counts["fused"])
    assert len(tr.of_type("prefill")) == total_chunks


def test_superstep_dispatch_and_sync_accounting(setup):
    """Acceptance: on a pure-decode phase at superstep=k, decode dispatches
    and host syncs are ceil(steps/k) — dispatches-per-token <= 1/k(1+eps)."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
               for _ in range(4)]
    max_new, k = 12, 4
    eng = ServeEngine(cfg, params, _scfg("serial", superstep=k))
    for p in prompts:
        eng.add_request(p, max_new_tokens=max_new)
    eng._admit()                           # prefill up front
    d0, s0 = eng.dispatch_counts["decode"], eng.host_syncs
    res = eng.run_until_done()
    steps = max_new                        # equal budgets: max_new rounds
    dispatches = eng.dispatch_counts["decode"] - d0
    syncs = eng.host_syncs - s0
    assert dispatches == math.ceil(steps / k)
    assert syncs <= steps / k
    assert dispatches / steps <= (1 / k) * 1.01
    assert all(len(v) == max_new for v in res.values())


def test_choose_superstep_from_queue_state(setup):
    """The scheduler only commits to a superstep when nothing is waiting,
    and clips it to the largest remaining generation budget."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, _scfg("serial", superstep=8))
    rng = np.random.default_rng(0)
    assert choose_superstep(eng) == 1      # nothing resident
    eng.add_request(rng.integers(0, cfg.vocab_size, 5), max_new_tokens=3)
    wave = eng.admit_wave()
    eng.prefill_wave(wave)
    assert choose_superstep(eng) == 3      # clipped to the remaining budget
    eng.add_request(rng.integers(0, cfg.vocab_size, 5), max_new_tokens=3)
    assert choose_superstep(eng) == 1      # queued request: stay responsive


# --------------------------------------------------------------------------- #
# schema v5: round-trip + v1/v2/v3/v4 upgrade in place
# --------------------------------------------------------------------------- #
def _downgrade(trace: Trace, version: int) -> str:
    """Strip the fields a pre-v5 (and optionally pre-v4/v3/v2) recorder
    would not have written."""
    header = json.loads(json.dumps(trace.header))
    header["version"] = version
    drop_serve = {4: (),
                  3: ("fuse", "superstep"),
                  2: ("fuse", "superstep", "pack", "max_prefill_jobs",
                      "decode_floor"),
                  1: ("fuse", "superstep", "pack", "max_prefill_jobs",
                      "decode_floor", "policy", "sub_batch")}[version]
    drop_ev = {4: ("arrival_offset",),
               3: ("arrival_offset", "fused", "superstep", "superstep_id"),
               2: ("arrival_offset", "fused", "superstep", "superstep_id",
                   "packed", "segments", "rows"),
               1: ("arrival_offset", "fused", "superstep", "superstep_id",
                   "packed", "segments", "rows", "sub_batch",
                   "overlap")}[version]
    for key in drop_serve:
        header["serve"].pop(key, None)
    lines = [json.dumps(header)]
    for e in trace.events:
        e = dict(e)
        for key in drop_ev:
            e.pop(key, None)
        lines.append(json.dumps(e))
    if trace.summary is not None:
        lines.append(json.dumps(trace.summary))
    return "\n".join(lines) + "\n"


def test_schema_v5_roundtrip(fused_superstep_serve, tmp_path):
    tr = fused_superstep_serve[1].to_trace()
    assert tr.version == 8            # current schema (v8: KV snapshots)
    assert all("arrival_offset" in e for e in tr.of_type("request"))
    assert tr.header["serve"]["fuse"] is True
    assert tr.header["serve"]["superstep"] == 4
    assert any(e["fused"] for e in tr.of_type("prefill"))
    dec = tr.of_type("decode")
    assert any(e["fused"] for e in dec)
    assert any(e["superstep"] > 1 and e["superstep_id"] >= 0 for e in dec)
    path = tmp_path / "t.jsonl"
    tr.save(path)
    tr2 = Trace.load(path)
    assert tr2.header == tr.header
    assert tr2.events == tr.events
    assert tr2.summary == tr.summary


@pytest.mark.parametrize("version", (1, 2, 3, 4))
def test_pre_v5_traces_upgrade_in_place(baseline, version):
    """v1/v2/v3/v4 traces load, upgrade to current semantics (fused=False,
    superstep=1/-1, header fuse=False, arrival_offset=0), and lower to
    identical command streams as their current-schema serial twin."""
    tr4 = baseline[1].to_trace()
    old = Trace.loads(_downgrade(tr4, version))
    assert old.version == version
    assert old.header["serve"]["fuse"] is False
    assert old.header["serve"]["superstep"] == 1
    for e in old.of_type("request"):
        assert e["arrival_offset"] == 0
    for e in old.of_type("prefill"):
        assert e["fused"] is False
    for e in old.of_type("decode"):
        assert e["fused"] is False
        assert e["superstep"] == 1 and e["superstep_id"] == -1
    lo_old = trace_to_commands(old)
    lo_new = trace_to_commands(tr4)
    assert len(lo_old) == len(lo_new)
    for a, b in zip(lo_old, lo_new):
        assert (a.phase, a.n_tokens, a.kv_len) == (b.phase, b.n_tokens,
                                                   b.kv_len)
        assert [c.name for c in a.commands] == [c.name for c in b.commands]


def test_v4_header_requires_fuse(baseline):
    tr = baseline[1].to_trace()
    header = json.loads(json.dumps(tr.header))
    del header["serve"]["fuse"]
    from repro.trace import TraceSchemaError
    with pytest.raises(TraceSchemaError):
        Trace.loads(json.dumps(header) + "\n")


# --------------------------------------------------------------------------- #
# replay: mixed fused / superstep / plain traces
# --------------------------------------------------------------------------- #
def test_replay_mixed_trace_preserves_coverage(fused_superstep_serve):
    """A trace mixing fused, superstep and plain steps lowers one
    LoweredStep per schedulable event, groups into the dispatch spans the
    engine actually ran, and replays with every step covered."""
    eng, rec, res = fused_superstep_serve
    tr = rec.to_trace()
    lowered = trace_to_commands(tr)
    assert len(lowered) == len(tr.schedulable)       # per-step coverage
    groups = group_dispatch_spans(lowered)
    fused_groups = [g for g in groups if len(g) > 1 and g[0].overlap]
    ss_groups = [g for g in groups if len(g) > 1 and not g[0].overlap]
    assert fused_groups and all(all(ls.fused for ls in g)
                                for g in fused_groups)
    assert ss_groups
    for g in ss_groups:                    # one dispatch's inner steps
        assert len({ls.superstep_id for ls in g}) == 1
        assert all(ls.phase == "generation" for ls in g)
        assert len(g) <= g[0].superstep
    assert sum(len(g) for g in groups) == len(lowered)
    rep = TraceReplayer().replay(lowered)
    assert rep.overlap_stats["fused_groups"] == len(fused_groups)
    assert rep.superstep_stats["spans"] == len(ss_groups)
    assert rep.superstep_stats["steps"] == sum(len(g) for g in ss_groups)
    assert rep.superstep_stats["gain"] > 0           # inner steps pipeline
    assert (sum(rep.phase_steps.values())
            == len(lowered) - sum(len(g) - 1 for g in fused_groups))
    # every generated token appears in exactly one decode event
    n_tok = sum(len(v) for v in res.values())
    assert sum(len(e["tokens"]) for e in tr.of_type("decode")) == n_tok
    assert rep.makespan > 0


def test_merge_streams_issue_modes(setup):
    """Chained issue roots model back-to-back host launches: one issue
    command per stream (chained), vs one shared root for a fused dispatch;
    the chained schedule is never faster."""
    full = get_arch("llama3.2-1b")
    sim = Simulator(SimConfig(trace=True, issue_overhead=0.1e-6))
    pf = graphs.build_stage(full, 32, 32, "summarization",
                            PASPolicy.paper(), lm_head=False)
    dec = graphs.build_stage(full, 3, 80, "generation", PASPolicy.paper())
    shared = merge_streams([pf, dec], mode="parallel", issue_mode="shared")
    chained = merge_streams([pf, dec], mode="parallel",
                            issue_mode="chained")
    assert len(shared) == len(pf) + len(dec) + 1
    assert len(chained) == len(pf) + len(dec) + 2
    r_shared = sim.run(shared)
    r_chained = sim.run(chained)
    assert r_chained.makespan >= r_shared.makespan * 0.999
    with pytest.raises(ValueError):
        merge_streams([pf, dec], mode="parallel", issue_mode="nope")


# --------------------------------------------------------------------------- #
# per-lane prefix spans: continuation lanes segregate into their own
# dispatches so short-prompt-only dispatches stop paying the prefix gather
# --------------------------------------------------------------------------- #
def _mk_wave(plens, slots=None):
    rng = np.random.default_rng(0)
    slots = slots or list(range(len(plens)))
    return [(s, Request(rid=i,
                        prompt=rng.integers(0, 100, p).astype(np.int32)))
            for i, (s, p) in enumerate(zip(slots, plens))]


def _kv_cells(job, chunk):
    return sum(d.rows * (d.prefix_span + chunk) for d in job.dispatches)


def test_planner_segregates_continuation_lanes():
    """With one multi-chunk prompt plus many shorts spilling over several
    dispatches, segregation keeps the short-only dispatches at span 0 —
    strictly fewer attended KV cells for the same coverage and the same
    dispatch count."""
    C = 8
    wave = _mk_wave([3 * C + 1] + [C // 2 + 1] * 7,
                    slots=list(range(8)))
    seg = plan_packed_job(wave, max_slots=2, chunk=C, sub_batch=0)
    naive = plan_packed_job(wave, max_slots=2, chunk=C, sub_batch=0,
                            segregate=False)
    assert seg.n_chunks == naive.n_chunks
    assert _kv_cells(seg, C) < _kv_cells(naive, C)
    spans = [d.prefix_span for d in seg.dispatches]
    assert spans == sorted(spans)          # span-free dispatches run first
    assert spans[0] == 0 and spans[-1] > 0
    # piece order still non-decreasing across dispatches per slot
    for slot, req in wave:
        seen = []
        for di, d in enumerate(seg.dispatches):
            for r in range(d.tokens.shape[0]):
                for j in np.nonzero(d.valid[r])[0]:
                    if int(d.seg_slot[r, j]) == slot:
                        seen.append((int(d.seg_pos[r, j]), di))
        seen.sort()
        assert [di for _p, di in seen] == sorted(di for _p, di in seen)


def test_engine_counts_saved_kv_reads(setup):
    """Acceptance (satellite): prefill_stats counts the attended KV cells,
    and the engine's segregated packed plan pays strictly fewer of them
    than the naive (unsegregated) layout of the same wave."""
    cfg, params = setup
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in (25, 5, 5, 5)]
    eng = ServeEngine(cfg, params,
                      _scfg("serial", pack=True, admission="fifo"))
    for p in prompts:
        eng.add_request(p, max_new_tokens=1)
    wave = eng.admit_wave()                # one wave: 1 long + 3 shorts
    job = eng.build_prefill_job(wave)
    while not job.done:
        eng.dispatch_prefill_chunk(job)
    naive = plan_packed_job(wave, max_slots=4, chunk=8, sub_batch=0,
                            segregate=False)
    assert eng.prefill_stats["kv_cells"] == _kv_cells(job, 8)
    assert eng.prefill_stats["kv_cells"] < _kv_cells(naive, 8)


# --------------------------------------------------------------------------- #
# real-length workloads
# --------------------------------------------------------------------------- #
def test_lengths_from_file_and_arrivals(setup):
    cfg, _ = setup
    dist = lengths_from_file(os.path.join(DATA_DIR, "chat_lengths.json"))
    assert dist.source
    rng = np.random.default_rng(0)
    ps = [dist.sample_prompt(rng) for _ in range(500)]
    os_ = [dist.sample_output(rng) for _ in range(500)]
    assert min(ps) >= dist.prompt_edges[0]
    assert max(ps) < dist.prompt_edges[-1]
    assert min(os_) >= dist.output_edges[0]
    assert max(os_) < dist.output_edges[-1]
    assert len(set(ps)) > 20               # not degenerate
    # generators draw from the empirical distribution, clipped to bounds
    arr = poisson_arrivals(1.0, 40, vocab=cfg.vocab_size,
                           prompt_len=(2, 48), max_new=(2, 12),
                           lengths=dist, seed=3)
    assert arr
    lens = [len(a.prompt) for a in arr]
    assert all(2 <= n <= 48 for n in lens)
    assert all(2 <= a.max_new <= 12 for a in arr)
    # same seed -> same workload; the empirical mix is not uniform-flat
    arr2 = poisson_arrivals(1.0, 40, vocab=cfg.vocab_size,
                            prompt_len=(2, 48), max_new=(2, 12),
                            lengths=dist, seed=3)
    assert [len(a.prompt) for a in arr2] == lens
    with pytest.raises(ValueError):
        lengths_from_file(os.path.join(DATA_DIR, "dispatch_baseline.json"))


def test_real_length_workload_serves(setup):
    """A chat-length workload drives the full fused+superstep engine."""
    cfg, params = setup
    dist = lengths_from_file(os.path.join(DATA_DIR, "chat_lengths.json"))
    arr = poisson_arrivals(0.4, 16, vocab=cfg.vocab_size,
                           prompt_len=(2, 40), max_new=(2, 6),
                           lengths=dist, seed=5)
    eng, _rec, res = _serve(cfg, params, "interleaved", arr, pack=True,
                            fuse=True, superstep=4)
    assert len(res) == len(arr)
    assert all(v for v in res.values())
