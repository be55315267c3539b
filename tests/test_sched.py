"""Phase-interleaving scheduler: policy equivalence, overlap accounting,
mapping-aware gating, stream merging, schema v2 compat, replay scoring."""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import IANUS_HW, merge_streams, route_fc_tpu
from repro.core.pas import PASPolicy
from repro.models import transformer as T
from repro.models.params import init_params
from repro.sched import (InterleavedScheduler, PimAwareScheduler,
                         SerialScheduler, make_scheduler)
from repro.serve import ServeConfig, ServeEngine
from repro.sim import SimConfig, Simulator
from repro.sim import graphs
from repro.trace import (Trace, TraceRecorder, TraceReplayer, drive,
                         group_overlapped, poisson_arrivals,
                         trace_to_commands)

KEY = jax.random.PRNGKey(0)
POLICIES = ("serial", "interleaved", "pim_aware")
FULL_DIMS = (2048, 8192)          # llama3.2-1b (pim_aware mapping dims)


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
def mixed_workload(setup):
    """One mixed-length open-loop workload served under all three policies
    (module-shared: the equivalence, accounting and replay tests all
    compare the same serves)."""
    cfg, params = setup
    arrivals = poisson_arrivals(0.5, 24, vocab=cfg.vocab_size,
                                prompt_len=(2, 40), max_new=(3, 8), seed=1)
    return {pol: _serve(cfg, params, pol, arrivals) for pol in POLICIES}


# --------------------------------------------------------------------------- #
# equivalence: scheduling must never change numerics
# --------------------------------------------------------------------------- #
def test_policies_emit_identical_greedy_tokens(mixed_workload):
    """Acceptance: serial / interleaved / pim_aware produce identical greedy
    tokens per request on a mixed-length workload — step composition changes
    the dispatch schedule, never the numerics."""
    results = {pol: r for pol, (_e, _rec, r) in mixed_workload.items()}
    assert results["serial"] == results["interleaved"]
    assert results["serial"] == results["pim_aware"]


def test_interleaved_overlaps_dispatches(mixed_workload):
    """The interleaved policy must actually co-schedule: most steps carry a
    prefill chunk riding a decode dispatch; serial never does."""
    serial = mixed_workload["serial"][0]
    inter = mixed_workload["interleaved"][0]
    assert serial.scheduler.stats["overlapped"] == 0
    assert inter.scheduler.stats["overlapped"] > 0
    # stats account for every engine step
    for eng in (serial, inter):
        assert sum(eng.scheduler.stats[k] for k in
                   ("overlapped", "serialized", "prefill_only",
                    "decode_only", "idle")) == eng.step_idx
    # one prefill chunk per interleaved step: chunk dispatches can never
    # exceed steps, and total generated tokens match the decode occupancies
    assert inter.dispatch_counts["prefill"] <= inter.step_idx
    # the serial engine admits the same requests in fewer, denser waves
    assert serial.scheduler.stats["serialized"] > 0   # admission steps


def test_scheduler_factory_and_fallbacks(setup):
    cfg, params = setup
    assert isinstance(make_scheduler("serial"), SerialScheduler)
    assert isinstance(make_scheduler("interleaved"), InterleavedScheduler)
    assert isinstance(make_scheduler("pim_aware"), PimAwareScheduler)
    with pytest.raises(ValueError):
        make_scheduler("nope")
    # SSM stacks can't chunk prefill -> interleaving degrades to serial
    rcfg = get_arch("rwkv6-7b").reduced()
    rparams = init_params(T.param_defs(rcfg), KEY)
    eng = ServeEngine(rcfg, rparams,
                      ServeConfig(max_slots=2, max_len=32,
                                  policy="interleaved"))
    assert eng.effective_policy == "serial"
    rng = np.random.default_rng(3)
    rids = [eng.add_request(rng.integers(0, rcfg.vocab_size, 4),
                            max_new_tokens=3) for _ in range(3)]
    res = eng.run_until_done()
    assert sorted(res) == sorted(rids)
    assert all(len(v) == 3 for v in res.values())


def test_sub_batch_caps_admission_wave(setup):
    """NeuPIMs-style sub-batching: sub_batch=1 admits one slot per wave, so
    waves never mix prompt lengths and tokens still match serial."""
    cfg, params = setup
    arrivals = poisson_arrivals(0.8, 10, vocab=cfg.vocab_size,
                                prompt_len=(2, 30), max_new=(2, 5), seed=4)
    _e1, _r1, serial = _serve(cfg, params, "serial", arrivals)
    eng, _r2, sub = _serve(cfg, params, "interleaved", arrivals, sub_batch=1)
    assert serial == sub
    waves = [e for e in _r2.events if e["type"] == "admit"]
    assert all(len(e["wave"]) == 1 for e in waves)


# --------------------------------------------------------------------------- #
# pim_aware: mapping-gated co-scheduling
# --------------------------------------------------------------------------- #
def test_pim_aware_gates_on_fc_mapping(mixed_workload):
    """pim_aware only overlaps steps whose phase FC mappings land on
    different engines; conflicting steps serialize. The mixed workload has
    both: full-size chunks (GEMM/MU) against small decodes (GEMV/PIM)
    overlap, small tail chunks (GEMV) against decodes conflict."""
    eng = mixed_workload["pim_aware"][0]
    sched = eng.scheduler
    assert sched.stats["overlapped"] > 0
    assert sched.stats["serialized"] > 0
    assert sched.decision_log
    for d in sched.decision_log:
        expect = d["prefill_route"] != d["decode_route"]
        assert d["overlap"] == expect
        # the log mirrors route_fc_tpu on the mapping dims
        assert d["prefill_route"] == route_fc_tpu(
            max(d["n_prefill"], 1), *FULL_DIMS, IANUS_HW)
        assert d["decode_route"] == route_fc_tpu(
            max(d["n_decode"], 1), *FULL_DIMS, IANUS_HW)
    # an interleaved engine overlaps at least as often as the gated one
    inter = mixed_workload["interleaved"][0]
    assert inter.scheduler.stats["overlapped"] \
        >= sched.stats["overlapped"]


# --------------------------------------------------------------------------- #
# double-buffered token fetch
# --------------------------------------------------------------------------- #
def test_double_buffered_fetch_sync_accounting(mixed_workload):
    """The decode fetch copies asynchronously at dispatch (async_fetches)
    and resolves exactly once per decode step: host_syncs == decode
    dispatches <= engine steps."""
    for pol, (eng, _rec, _res) in mixed_workload.items():
        assert eng.host_syncs == eng.dispatch_counts["decode"]
        assert eng.host_syncs <= eng.step_idx
        assert eng.async_fetches == eng.host_syncs


# --------------------------------------------------------------------------- #
# merge_streams: overlapped / pipelined command-DAG composition
# --------------------------------------------------------------------------- #
def test_merge_streams_parallel_bounds(setup):
    full = get_arch("llama3.2-1b")
    sim = Simulator(SimConfig(trace=True, issue_overhead=0.1e-6))
    pf = graphs.build_stage(full, 32, 32, "summarization",
                            PASPolicy.paper(), lm_head=False)
    dec = graphs.build_stage(full, 3, 80, "generation", PASPolicy.paper())
    solo = sim.run(pf).makespan + sim.run(dec).makespan
    merged = sim.run_streams([pf, dec], "parallel")
    assert merged.n_commands == len(pf) + len(dec) + 1    # + step_issue root
    assert merged.makespan < solo
    assert merged.makespan >= max(sim.run(pf).makespan,
                                  sim.run(dec).makespan) * 0.999
    # all commands still execute; per-stream prefixes are disjoint
    names = [n for _s, _e, _u, n, _t in merged.trace]
    assert any(n.startswith("s0.") for n in names)
    assert any(n.startswith("s1.") for n in names)


def test_merge_streams_pipelined_prefetches_weights(setup):
    """Cross-step pipelining: step k+1's FC weight loads may start during
    step k (static operands); its compute stays chained behind step k."""
    full = get_arch("llama3.2-1b")
    sim = Simulator(SimConfig(trace=True, issue_overhead=0.1e-6))
    d1 = graphs.build_stage(full, 1, 80, "generation", PASPolicy.paper())
    d2 = graphs.build_stage(full, 1, 81, "generation", PASPolicy.paper())
    solo = sim.run(d1).makespan + sim.run(d2).makespan
    piped = sim.run_streams([d1, d2], "pipelined")
    assert piped.makespan <= solo
    s0_end = max(e for _s, e, _u, n, _t in piped.trace
                 if n.startswith("s0."))
    early_w = [n for s, _e, _u, n, _t in piped.trace
               if n.startswith("s1.") and ".w" in n and s < s0_end]
    assert early_w                       # prefetch crossed the step boundary
    early_compute = [n for s, _e, u, n, _t in piped.trace
                     if n.startswith("s1.") and s < s0_end
                     and (u.startswith("MU") or u.startswith("VU")
                          or u == "PIM")]
    assert not early_compute             # compute did not
    with pytest.raises(ValueError):
        merge_streams([d1, d2], mode="sideways")


# --------------------------------------------------------------------------- #
# schema v2 + v1 backward compat
# --------------------------------------------------------------------------- #
def _downgrade_to_v1(trace: Trace) -> str:
    """Strip the v2 fields a PR-2-era recorder would not have written."""
    header = json.loads(json.dumps(trace.header))
    header["version"] = 1
    for k in ("policy", "sub_batch"):
        header["serve"].pop(k, None)
    lines = [json.dumps(header)]
    for e in trace.events:
        e = dict(e)
        for k in ("sub_batch", "overlap"):
            e.pop(k, None)
        lines.append(json.dumps(e))
    if trace.summary is not None:
        lines.append(json.dumps(trace.summary))
    return "\n".join(lines) + "\n"


def test_schema_v2_records_policy_and_overlap(mixed_workload):
    tr = mixed_workload["interleaved"][1].to_trace()
    assert tr.version == 8                 # current schema (v8: KV snapshots)
    assert tr.header["serve"]["policy"] == "interleaved"
    assert tr.header["serve"]["pack"] is False
    assert all("sub_batch" in e and "overlap" in e
               for e in tr.of_type("prefill"))
    assert all("overlap" in e for e in tr.of_type("decode"))
    assert any(e["overlap"] for e in tr.of_type("decode"))
    # sub-batch ids are admission-wave ordinals: nondecreasing, one per wave
    subs = [e["sub_batch"] for e in tr.of_type("prefill")]
    assert subs == sorted(subs)
    assert len(set(subs)) == len(tr.of_type("admit"))


def test_schema_v1_loads_and_lowers_identically(mixed_workload, tmp_path):
    """Back-compat: a v1 (PR-2 era) trace still loads — events are upgraded
    with serial-semantics defaults — and lowers to the same command streams
    as its v2 serial twin."""
    tr2 = mixed_workload["serial"][1].to_trace()
    v1_text = _downgrade_to_v1(tr2)
    v1 = Trace.loads(v1_text)
    assert v1.version == 1
    assert v1.header["serve"]["policy"] == "serial"     # upgraded default
    assert all(not e["overlap"] for e in v1.schedulable)
    l1 = trace_to_commands(v1)
    l2 = trace_to_commands(Trace.loads(tr2.dumps()))
    assert len(l1) == len(l2)
    for a, b in zip(l1, l2):
        assert a.commands == b.commands
        assert not a.overlap
    # round trip: a loaded v1 trace re-serializes and re-loads cleanly
    p = tmp_path / "v1.jsonl"
    v1.save(p)
    again = Trace.load(p)
    assert again.events == v1.events
    # a v2 trace missing its required v2 keys is rejected
    bad = dict(tr2.events and next(e for e in tr2.events
                                   if e["type"] == "decode"))
    bad.pop("overlap")
    from repro.trace import TraceSchemaError
    with pytest.raises(TraceSchemaError):
        Trace.loads(json.dumps(tr2.header) + "\n" + json.dumps(bad))


# --------------------------------------------------------------------------- #
# replay: overlapped steps score as merged DAGs; interleaved beats serial
# --------------------------------------------------------------------------- #
def test_overlap_groups_follow_trace_flags(mixed_workload):
    lowered = trace_to_commands(mixed_workload["interleaved"][1].to_trace())
    groups = group_overlapped(lowered)
    assert sum(len(g) for g in groups) == len(lowered)
    multi = [g for g in groups if len(g) > 1]
    assert multi
    for g in multi:
        assert all(ls.overlap for ls in g)
        assert len({ls.step for ls in g}) == 1
        assert {ls.phase for ls in g} == {"summarization", "generation"}
    # serial trace: singleton groups only
    sl = trace_to_commands(mixed_workload["serial"][1].to_trace())
    assert all(len(g) == 1 for g in group_overlapped(sl))


def test_interleaved_replay_beats_serial(mixed_workload):
    """Acceptance: on the mixed-arrival workload, the interleaved policy's
    replayed makespan beats serial at paper-scale dims, with strictly higher
    combined NPU+PIM utilization, while serving identical tokens."""
    full = get_arch("llama3.2-1b")
    reps = {}
    for pol in ("serial", "interleaved"):
        lowered = trace_to_commands(mixed_workload[pol][1].to_trace(),
                                    cfg=full)
        reps[pol] = TraceReplayer().replay(lowered)
    serial, inter = reps["serial"], reps["interleaved"]
    assert inter.makespan < serial.makespan
    assert inter.overlap_stats["groups"] > 0
    assert inter.overlap_stats["gain"] > 0
    assert serial.overlap_stats["groups"] == 0

    def combined(rep):
        return (rep.result.group_utilization("MU")
                + rep.result.group_utilization("PIM"))
    assert combined(inter) > combined(serial)
    assert inter.result.group_utilization("PIM") > 0.2
    # the breakdown stays valid: overlapped phase accounted, tags exposed
    assert inter.phase_time["overlapped"] > 0.0
    assert inter.makespan == pytest.approx(
        inter.phase_time["summarization"] + inter.phase_time["generation"]
        + inter.phase_time["overlapped"])
    for tag in ("ffn", "self_attn", "norm_res"):
        assert inter.exposed_tags.get(tag, 0.0) > 0.0
    json.dumps(inter.to_dict())


def test_cross_step_pipelining_gains(mixed_workload):
    """ROADMAP 'cross-step pipelining': chaining the served steps into one
    pipelined DAG (next step's weight prefetch during the current step's
    tail) must beat back-to-back composition."""
    lowered = trace_to_commands(mixed_workload["serial"][1].to_trace())
    flat = TraceReplayer().replay(lowered)
    piped = TraceReplayer().replay(lowered, cross_step=True)
    assert piped.pipeline is not None
    assert piped.pipeline["gain"] > 0
    assert piped.makespan == pytest.approx(piped.pipeline["makespan"])
    assert piped.makespan < flat.makespan
    assert flat.pipeline is None
