"""Incremental KV snapshots: checkpoint-based failover that re-prefills
only the suffix — byte-identity across policies, durability modes, the
snapshot-provenance audit, clamped backoff, tolerant trace loading, and
cost-model-derived fault plans."""
import json
import os

import jax
import numpy as np
import pytest

from repro.chaos import (FaultEvent, FaultPlan, SnapshotStore,
                         serve_fleet_chaos)
from repro.configs import get_arch
from repro.fleet import FleetMetrics, serve_fleet
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve import ServeConfig, ServeEngine
from repro.trace import drive
from repro.trace.arrivals import bursty_arrivals
from repro.trace.schema import (SCHEMA_VERSION, Trace, TraceSchemaError,
                                upgrade_event, validate_event)
from repro.verify import (check_exactly_once, check_snapshot_provenance,
                          lint_trace)

KEY = jax.random.PRNGKey(0)
FULL_DIMS = (2048, 8192)
REPLICAS = 3

# crash node 1 mid-superstep (step 9 with superstep=4: supersteps span
# [8, 12) on the fleet clock) with snapshots due every 4 ticks, plus a
# degraded window so restore composes with PIM-degraded serving
SNAP_PLAN = FaultPlan(events=[
    FaultEvent("node_crash", 1, 9),
    FaultEvent("pim_degraded", 0, 4, until=20),
])


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("llama3.2-1b").reduced()
    params = init_params(T.param_defs(cfg), KEY)
    return cfg, params


@pytest.fixture(scope="module")
def arrivals(setup):
    cfg, _ = setup
    return bursty_arrivals(1.0, 24, vocab=cfg.vocab_size, burst=6, idle=6,
                           prompt_len=(2, 40), max_new=(3, 8), seed=3)


def _scfg(**kw):
    base = dict(max_slots=4, max_len=64, prefill_chunk=8,
                policy="pim_aware", pack=True, fuse=True, superstep=4,
                map_dims=FULL_DIMS)
    base.update(kw)
    return ServeConfig(**base)


def _run(setup, arrivals, scfg, plan, **kw):
    cfg, params = setup
    kw.setdefault("replicas", REPLICAS)
    kw.setdefault("routing", "least_loaded")
    return serve_fleet_chaos(cfg, params, scfg, arrivals, plan, **kw)


@pytest.fixture(scope="module")
def snap_run(setup, arrivals, tmp_path_factory):
    """The reference snapshot-enabled chaos run: mirrored AND disk-backed,
    so both durability paths are live in one trace set."""
    d = tmp_path_factory.mktemp("snapstore")
    return _run(setup, arrivals, _scfg(), SNAP_PLAN, snapshot_interval=4,
                snapshot_mirror=True, snapshot_dir=str(d))


# --------------------------------------------------------------------------- #
# tentpole: byte-identity across policies x pack x fuse x superstep
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("policy,pack,fuse,superstep", [
    ("serial", True, False, 1),
    ("interleaved", True, True, 4),
    ("pim_aware", False, True, 4),     # unpacked suffix re-prefill path
    ("pim_aware", True, True, 4),
])
def test_snapshot_restore_tokens_identical(setup, arrivals, policy, pack,
                                           fuse, superstep):
    cfg, params = setup
    scfg = _scfg(policy=policy, pack=pack, fuse=fuse, superstep=superstep)
    ref = serve_fleet(cfg, params, scfg, arrivals, replicas=REPLICAS,
                      routing="least_loaded").tokens_by_gid()
    res = _run(setup, arrivals, scfg, SNAP_PLAN, snapshot_interval=4,
               snapshot_mirror=True)
    assert not res.failed and not res.rejected
    got = res.tokens_by_gid()
    assert set(got) == set(range(len(arrivals)))
    for gid, toks in got.items():
        assert toks == ref[gid], (policy, pack, fuse, superstep, gid)
    # the crash genuinely exercised the restore path: some recovery was
    # seeded from a snapshot and re-prefilled strictly less than from-zero
    assert res.recoveries
    assert any(r["restored_tokens"] > 0 for r in res.recoveries)
    for r in res.recoveries:
        if r["restored_tokens"]:
            assert r["snapshot_step"] is not None
            assert r["snapshot_step"] < r["crash_step"]
            assert r["reprefill_tokens"] < r["restored_tokens"] \
                + r["reprefill_tokens"]
    traces = list(res.traces.values())
    assert check_exactly_once(traces) == []
    assert check_snapshot_provenance(traces) == []
    for tr in traces:
        assert [f for f in lint_trace(tr) if f.severity == "error"] == []


def test_snapshot_restore_saves_reprefill_vs_from_zero(setup, arrivals,
                                                       snap_run):
    """The headline claim: with snapshots the fleet pays strictly fewer
    re-prefill tokens than PR 9's from-zero recovery of the same crash."""
    zero = _run(setup, arrivals, _scfg(), SNAP_PLAN)
    res = snap_run
    assert res.tokens_by_gid() == zero.tokens_by_gid()
    by_gid = {r["gid"]: r for r in res.recoveries}
    zero_by_gid = {r["gid"]: r for r in zero.recoveries}
    assert set(by_gid) == set(zero_by_gid)
    for gid, r in by_gid.items():
        z = zero_by_gid[gid]
        assert z["restored_tokens"] == 0 and z["snapshot_step"] is None
        # saved + paid equals the from-zero cost, token for token
        assert r["restored_tokens"] + r["reprefill_tokens"] == \
            z["reprefill_tokens"]
    assert sum(r["reprefill_tokens"] for r in res.recoveries) < \
        sum(r["reprefill_tokens"] for r in zero.recoveries)


def test_snapshot_run_is_bit_deterministic(setup, arrivals, snap_run,
                                           tmp_path):
    again = _run(setup, arrivals, _scfg(), SNAP_PLAN, snapshot_interval=4,
                 snapshot_mirror=True, snapshot_dir=str(tmp_path))
    assert again.assignments == snap_run.assignments
    assert again.recoveries == snap_run.recoveries
    assert again.tokens_by_gid() == snap_run.tokens_by_gid()
    for n in snap_run.traces:
        assert again.traces[n].events == snap_run.traces[n].events


def test_crash_before_first_snapshot_equals_from_zero(setup, arrivals):
    """A snapshot interval longer than the run never fires: recovery must
    degrade to PR 9's from-zero path, recovery for recovery."""
    zero = _run(setup, arrivals, _scfg(), SNAP_PLAN)
    res = _run(setup, arrivals, _scfg(), SNAP_PLAN, snapshot_interval=500)
    assert res.tokens_by_gid() == zero.tokens_by_gid()
    assert res.recoveries == zero.recoveries
    assert all(r["restored_tokens"] == 0 and r["snapshot_step"] is None
               for r in res.recoveries)
    assert res.snapshots is not None and res.snapshots["puts"] == 0
    assert check_snapshot_provenance(list(res.traces.values())) == []


def test_inmemory_snapshots_without_mirror_fall_back(setup, arrivals):
    """In-memory-only records die with their owner: the crashed node's
    snapshots cannot seed restores, so recovery is from zero — but still
    byte-identical, and the provenance pass stays clean (no restore claims
    a record that could not have survived)."""
    zero = _run(setup, arrivals, _scfg(), SNAP_PLAN)
    res = _run(setup, arrivals, _scfg(), SNAP_PLAN, snapshot_interval=4)
    assert res.tokens_by_gid() == zero.tokens_by_gid()
    assert all(r["restored_tokens"] == 0 for r in res.recoveries)
    assert res.snapshots["dropped"] > 0
    assert check_snapshot_provenance(list(res.traces.values())) == []


def test_disk_backed_snapshots_survive_without_mirror(setup, arrivals,
                                                      snap_run, tmp_path):
    """Disk backing alone (no mirror) restores through the atomic-save
    round trip — the dropped payload lazily reloads from the npz."""
    res = _run(setup, arrivals, _scfg(), SNAP_PLAN, snapshot_interval=4,
               snapshot_dir=str(tmp_path))
    assert res.tokens_by_gid() == snap_run.tokens_by_gid()
    assert any(r["restored_tokens"] > 0 for r in res.recoveries)
    assert res.snapshots["disk_writes"] > 0
    assert res.snapshots["disk_loads"] > 0
    assert check_snapshot_provenance(list(res.traces.values())) == []


# --------------------------------------------------------------------------- #
# schema v8: snapshot/restore events, admit restores, upgrade path
# --------------------------------------------------------------------------- #
def test_schema_v8_snapshot_events_round_trip(snap_run):
    for tr in snap_run.traces.values():
        assert tr.header["version"] == SCHEMA_VERSION == 8
        tr.validate()
        assert Trace.loads(tr.dumps()).events == tr.events
    ev = [e for t in snap_run.traces.values() for e in t.events]
    snaps = [e for e in ev if e["type"] == "snapshot"]
    rsts = [e for e in ev if e["type"] == "restore"]
    assert snaps and rsts
    for s in snaps:
        assert s["bytes"] > 0 and 0 <= s["base"] < s["prefix_len"]
    admits = [e for e in ev if e["type"] == "admit" and e["restores"]]
    assert admits, "restored admissions are visible in admit events"
    for a in admits:
        for slot, rid, plen in a["restores"]:
            assert plen > 0 and slot in a["wave"] or rid >= 0


def test_upgrade_v7_events_to_v8():
    adm = {"type": "admit", "step": 3, "wave": [0]}
    up = upgrade_event(dict(adm), 7)
    assert up["restores"] == []
    validate_event(up, SCHEMA_VERSION)
    rec = {"type": "recover", "step": 9, "gid": 1, "rid": 2,
           "from_node": 1, "crash_step": 8, "prefix_tokens": 3,
           "reprefill_tokens": 10, "retry": 1}
    up = upgrade_event(dict(rec), 7)
    assert up["restored_tokens"] == 0
    validate_event(up, SCHEMA_VERSION)
    with pytest.raises(TraceSchemaError):
        validate_event({"type": "snapshot", "step": 4, "gid": 0,
                        "prefix_len": 8}, SCHEMA_VERSION)   # bytes missing


# --------------------------------------------------------------------------- #
# provenance audit: tampered traces are caught
# --------------------------------------------------------------------------- #
def _copy_traces(res):
    return {n: Trace(header=dict(t.header),
                     events=[dict(e) for e in t.events],
                     summary=t.summary) for n, t in res.traces.items()}


def _tamper(res, klass, mutate):
    traces = _copy_traces(res)
    mutate(traces)
    got = {f.klass for f in
           check_snapshot_provenance(list(traces.values()))}
    assert klass in got, (klass, got)


def test_provenance_catches_tampering(snap_run):
    res = snap_run
    restored_node = next(n for n, t in res.traces.items()
                         if any(e["type"] == "restore" for e in t.events))

    def drop_restore(traces):
        evs = traces[restored_node].events
        evs[:] = [e for e in evs if e["type"] != "restore"]
    _tamper(res, "restore_missing", drop_restore)

    def late_snapshot(traces):
        for t in traces.values():
            for e in t.events:
                if e["type"] == "restore":
                    e["snapshot_step"] = e["step"] + 100
    _tamper(res, "snapshot_after_crash", late_snapshot)

    def early_snapshot(traces):
        # a snapshot_step before the first export: the chain up to it
        # covers [0, 0), far short of the restored prefix
        for t in traces.values():
            for e in t.events:
                if e["type"] == "restore":
                    e["snapshot_step"] = 0
    _tamper(res, "uncovered_restore", early_snapshot)

    restored_gids = {e["gid"] for t in res.traces.values()
                     for e in t.events if e["type"] == "restore"}

    def gap_chain(traces):
        # only restored gids' chains are walked; a base that is neither
        # the prior chain prefix nor 0 is a gap
        for t in traces.values():
            for e in t.events:
                if e["type"] == "snapshot" and e["gid"] in restored_gids:
                    e["base"] += 1
    _tamper(res, "snapshot_chain_gap", gap_chain)

    def bad_accounting(traces):
        for t in traces.values():
            for e in t.events:
                if e["type"] == "recover":
                    e["reprefill_tokens"] += 1
    _tamper(res, "reprefill_accounting", bad_accounting)

    def bad_prefix(traces):
        for t in traces.values():
            for e in t.events:
                if e["type"] == "recover" and e["prefix_tokens"] > 0:
                    e["prefix_tokens"] -= 1
    _tamper(res, "prefix_mismatch", bad_prefix)

    def not_durable(traces):
        for t in traces.values():
            for e in t.events:
                if e["type"] == "snapshot":
                    e["durable"] = False
                    e["mirror_node"] = None
    _tamper(res, "nondurable_snapshot", not_durable)

    def drop_recover(traces):
        for t in traces.values():
            t.events[:] = [e for e in t.events if e["type"] != "recover"]
    _tamper(res, "restore_unmoored", drop_recover)


# --------------------------------------------------------------------------- #
# SnapshotStore unit behavior
# --------------------------------------------------------------------------- #
def _entry(gid, base, plen, val=1.0):
    rows = np.full((2, plen - base, 3, 4), val, np.float32)
    return {"gid": gid, "rid": gid, "slot": 0, "base": base,
            "prefix_len": plen, "cache": {"L0.k": rows},
            "bytes": int(rows.nbytes), "plen": plen, "generated": [],
            "max_new": 4, "last_tok": 0, "lens": [plen], "rng": None}


def test_store_merges_deltas_contiguously(tmp_path):
    st = SnapshotStore()
    st.put(0, [_entry(7, 0, 5, 1.0)], tick=4)
    st.put(0, [_entry(7, 5, 9, 2.0)], tick=8)
    assert st.since(0) == {7: 9}
    rec = st.lookup(7)
    merged = rec["cache"]["L0.k"]
    assert merged.shape[1] == 9
    assert (merged[:, :5] == 1.0).all() and (merged[:, 5:] == 2.0).all()
    with pytest.raises(AssertionError):
        st.put(0, [_entry(7, 7, 12)], tick=12)     # gap in the delta chain


def test_store_crash_durability_matrix(tmp_path):
    # in-memory only: dies with the owner
    st = SnapshotStore()
    st.put(0, [_entry(1, 0, 4)], tick=4)
    st.drop_node(0)
    assert st.lookup(1) is None and st.stats["dropped"] == 1
    # mirrored: survives while the mirror is alive, dies with it
    st = SnapshotStore()
    st.put(0, [_entry(2, 0, 4)], tick=4, mirror_node=1)
    st.drop_node(0, alive=lambda n: n != 0)
    assert st.lookup(2) is not None
    st.put(0, [_entry(3, 0, 4)], tick=8, mirror_node=1)
    st.drop_node(1, alive=lambda n: n == 2)        # mirror gone first
    st.drop_node(0, alive=lambda n: n == 2)
    assert st.lookup(3) is None
    # disk-backed: crash drops the payload, lookup reloads the merged npz
    st = SnapshotStore(root=str(tmp_path))
    st.put(0, [_entry(4, 0, 4, 3.0)], tick=4)
    st.put(0, [_entry(4, 4, 6, 5.0)], tick=8)
    assert st.stats["disk_writes"] == 2
    st.drop_node(0)
    assert st.records[4]["cache"] is None
    rec = st.lookup(4)
    assert st.stats["disk_loads"] == 1
    got = rec["cache"]["L0.k"]
    assert got.shape[1] == 6
    assert (got[:, :4] == 3.0).all() and (got[:, 4:] == 5.0).all()
    # reassign moves ownership; drop removes the on-disk dir too
    st.reassign(4, 2)
    assert st.since(2) == {4: 6} and st.since(0) == {}
    path = st.records[4]["path"]
    st.drop(4)
    assert st.lookup(4) is None and not os.path.exists(path)


def test_engine_export_import_round_trip(setup):
    """Exported rows re-imported into a fresh engine's slot reproduce the
    source cache region exactly — the byte-identity primitive."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, _scfg())
    assert eng.snapshot_supported
    rng = np.random.default_rng(9)
    eng.add_request(rng.integers(0, cfg.vocab_size, 12), 30, gid=0)
    for _ in range(3):
        eng.step()
    entries = eng.export_kv_snapshot()
    assert entries and entries[0]["base"] == 0
    e = entries[0]
    # a second export with the high-water map is empty (pure delta)
    assert eng.export_kv_snapshot(since={0: e["prefix_len"]}) == []
    other = ServeEngine(cfg, params, _scfg())
    other.import_kv_snapshot(2, {"prefix_len": e["prefix_len"],
                                 "cache": e["cache"], "bytes": e["bytes"],
                                 "snapshot_step": 0})
    from repro.serve.engine import _flatten_cache
    src = _flatten_cache(eng.cache)
    dst = _flatten_cache(other.cache)
    P = e["prefix_len"]
    for k in src:
        np.testing.assert_array_equal(
            np.asarray(src[k][:, e["slot"], :P]),
            np.asarray(dst[k][:, 2, :P]))
    assert other.snapshot_stats["restores"] == 1
    assert other.snapshot_stats["restored_tokens"] == P


@pytest.mark.parametrize("superstep", [1, 4])
def test_snapshot_import_continues_after_donated_steps(setup, superstep):
    """KV rows exported from an engine whose step programs update the cache
    in place, imported into another engine's slot, continue the request
    with exactly the tokens an uninterrupted serve emits."""
    cfg, params = setup
    scfg = _scfg(superstep=superstep)
    prompt = np.random.default_rng(4).integers(0, cfg.vocab_size, 13)
    ref = ServeEngine(cfg, params, scfg)
    rid = ref.add_request(prompt, 12, gid=0)
    want = ref.run_until_done()[rid]
    src = ServeEngine(cfg, params, scfg)
    src.add_request(prompt, 12, gid=0)
    for _ in range(3):
        src.step()
    e = src.export_kv_snapshot()[0]
    assert e["generated"] and e["prefix_len"] > len(prompt) - 1
    dst = ServeEngine(cfg, params, scfg)
    full = np.concatenate([prompt, np.asarray(e["generated"], np.int32)])
    rid = dst.add_request(full, 12 - len(e["generated"]), gid=0, restore={
        "prefix_len": e["prefix_len"], "cache": e["cache"],
        "bytes": e["bytes"], "snapshot_step": 0})
    got = dst.run_until_done()[rid]
    assert e["generated"] + got == want
    assert dst.snapshot_stats["restored_tokens"] == e["prefix_len"]


# --------------------------------------------------------------------------- #
# satellite: clamped exponential backoff
# --------------------------------------------------------------------------- #
def test_backoff_cap_validation(setup, arrivals):
    cfg, params = setup
    with pytest.raises(ValueError):
        serve_fleet_chaos(cfg, params, _scfg(), arrivals, FaultPlan(),
                          replicas=2, backoff=4, backoff_cap=2)
    with pytest.raises(ValueError):
        drive(ServeEngine(cfg, params, _scfg()), arrivals, backoff=8,
              backoff_cap=4)


def test_drive_backoff_clamps_and_drains(setup, arrivals):
    """A tight cap keeps retry cadence bounded: the capped run drains with
    the same greedy tokens and no arrival lost, in no more engine steps
    than the uncapped doubling would take."""
    cfg, params = setup
    ref = drive(ServeEngine(cfg, params, _scfg()), arrivals)
    eng = ServeEngine(cfg, params, _scfg(queue_cap=1))
    res, stats = drive(eng, arrivals, backoff=1, backoff_cap=2,
                       return_stats=True)
    assert stats["rejected"] > 0
    assert len(res) == len(arrivals)
    assert sorted(map(tuple, res.values())) == \
        sorted(map(tuple, ref.values()))
    capped_steps = eng.step_idx
    eng2 = ServeEngine(cfg, params, _scfg(queue_cap=1))
    drive(eng2, arrivals, backoff=1, backoff_cap=4096)
    assert capped_steps <= eng2.step_idx


def test_chaos_backoff_cap_recorded_and_drains(setup, arrivals):
    plan = FaultPlan(events=[
        FaultEvent("queue_reject", n, 0, until=6, cap=0)
        for n in range(REPLICAS)])
    res = _run(setup, arrivals, _scfg(), plan, retry_budget=8, backoff=2,
               backoff_cap=4)
    assert not res.failed and not res.rejected
    for tr in res.traces.values():
        assert tr.header["chaos"]["backoff_cap"] == 4
    fm = FleetMetrics.from_traces(res.traces)
    assert fm.chaos_summary()["goodput"] == 1.0


# --------------------------------------------------------------------------- #
# satellite: tolerant trace loading (strict=False)
# --------------------------------------------------------------------------- #
def test_trace_load_skips_corrupt_interior_lines(snap_run, tmp_path):
    tr = next(iter(snap_run.traces.values()))
    lines = tr.dumps().splitlines()
    assert len(lines) > 6
    lines.insert(3, "{not json at all")                  # corrupt JSON
    lines.insert(6, json.dumps({"type": "decode", "step": 1}))  # bad schema
    path = str(tmp_path / "corrupt.jsonl")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises((TraceSchemaError, json.JSONDecodeError)):
        Trace.load(path)                                 # strict default
    with pytest.warns(RuntimeWarning):
        got = Trace.load(path, strict=False)
    assert got.skipped_lines == 2
    assert got.events == tr.events
    assert got.summary == tr.summary
    # a corrupt HEADER stays fatal even when tolerant: nothing downstream
    # is interpretable without it
    broken = str(tmp_path / "noheader.jsonl")
    with open(broken, "w") as f:
        f.write("{broken header\n" + "\n".join(lines[1:]) + "\n")
    with pytest.raises((TraceSchemaError, json.JSONDecodeError)):
        Trace.load(broken, strict=False)


def test_stats_cli_reports_skipped_lines(snap_run, tmp_path, capsys):
    from repro.launch.stats import _load_trace
    tr = next(iter(snap_run.traces.values()))
    lines = tr.dumps().splitlines()
    lines.insert(2, "garbage")
    path = str(tmp_path / "n0.jsonl")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.warns(RuntimeWarning):
        got = _load_trace(path)
    assert got.skipped_lines == 1
    assert "skipped 1 corrupt line(s)" in capsys.readouterr().out


# --------------------------------------------------------------------------- #
# metrics: live == offline, snapshot accounting
# --------------------------------------------------------------------------- #
def test_snapshot_metrics_live_offline_parity(snap_run, arrivals):
    live = FleetMetrics()
    for n, h in snap_run.hubs.items():
        live.add(n, h)
    offline = FleetMetrics.from_traces(snap_run.traces)
    c_live, c_off = live.chaos_summary(), offline.chaos_summary()
    assert c_live == c_off
    assert c_live["goodput"] == 1.0
    sn = c_live["snapshots"]
    assert sn["events"] > 0 and sn["bytes"] > 0 and sn["rows"] > 0
    assert sn["restores"] > 0 and sn["restore_hit_rate"] > 0
    assert sn["saved_tokens"] == \
        sum(r["restored_tokens"] for r in snap_run.recoveries)
    assert sn["paid_tokens"] == \
        sum(r["reprefill_tokens"] for r in snap_run.recoveries)
    assert c_live["restored_tokens"] == sn["saved_tokens"]
    assert sn["restore_prefix_len"]["count"] == sn["restores"]


# --------------------------------------------------------------------------- #
# cost-model-derived fault plans
# --------------------------------------------------------------------------- #
def _hot_sim():
    return {"makespan": 1.0,
            "utilization": {"PIM": 0.9, "MU": 0.5},
            "energy": {"mu_flops": 1e6, "vu_elems": 1e5,
                       "dram_bytes": 1e6, "pim_bytes": 1e6}}


def test_from_cost_model_is_deterministic_and_thresholded():
    a = FaultPlan.from_cost_model(_hot_sim(), 5, replicas=3, horizon=32)
    b = FaultPlan.from_cost_model(_hot_sim(), 5, replicas=3, horizon=32)
    assert a.to_dict() == b.to_dict()
    assert a.to_dict() != FaultPlan.from_cost_model(
        _hot_sim(), 6, replicas=3, horizon=32).to_dict()
    a.validate(3)
    kinds = [e.kind for e in a.events]
    assert "pim_degraded" in kinds and "slow_node" in kinds
    assert "node_crash" not in kinds          # cost model never crashes
    slow = next(e for e in a.events if e.kind == "slow_node")
    assert slow.factor >= 2
    # round-trips through JSON like any hand-written plan
    assert FaultPlan.from_dict(a.to_dict()).to_dict() == a.to_dict()
    # a cool cost model derives an empty plan
    cool = {"makespan": 1.0, "utilization": {"PIM": 0.1},
            "energy": {"mu_flops": 0.0, "vu_elems": 0.0,
                       "dram_bytes": 0.0, "pim_bytes": 0.0}}
    assert FaultPlan.from_cost_model(cool, 5).events == []


def test_from_cost_model_accepts_sim_result():
    """The classmethod takes a real SimResult object too, and derives the
    same plan from the object as from its to_dict() export."""
    from repro.sim import SimResult
    sim = SimResult(makespan=1.0,
                    unit_busy={"PIM0": 0.95, "MU0": 0.5},
                    tag_time={},
                    energy={"mu_flops": 1e6, "vu_elems": 1e5,
                            "dram_bytes": 1e6, "pim_bytes": 1e6})
    plan = FaultPlan.from_cost_model(sim, 7, replicas=2, horizon=24)
    plan.validate(2)
    assert plan.events
    assert plan.to_dict() == FaultPlan.from_cost_model(
        sim.to_dict(), 7, replicas=2, horizon=24).to_dict()
