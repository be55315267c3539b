"""Phase-separated serving: batched prefill equivalence + dispatch shape."""
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve import ServeConfig, ServeEngine
from repro.serve import engine as engine_mod
from repro.trace import TraceRecorder

KEY = jax.random.PRNGKey(0)


def _engine(cfg, params, mode="batched", chunk=8, slots=3, max_len=64,
            recorder=None):
    return ServeEngine(cfg, params,
                       ServeConfig(max_slots=slots, max_len=max_len,
                                   prefill_mode=mode, prefill_chunk=chunk),
                       recorder=recorder)


def _routes(rec):
    """The PAS route of every recorded prefill and decode event."""
    return [e["route"] for e in rec.events
            if e["type"] in ("prefill", "decode")]


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("llama3.2-1b").reduced()
    params = init_params(T.param_defs(cfg), KEY)
    return cfg, params


def test_batched_matches_sequential_mixed_lengths(setup):
    """A multi-request batch with mixed prompt lengths must generate
    identical greedy tokens through both prefill paths."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in (5, 17, 1, 30, 9, 2)]   # spans chunk boundaries
    results = {}
    for mode in ("sequential", "batched"):
        eng = _engine(cfg, params, mode)
        for p in prompts:
            eng.add_request(p, max_new_tokens=6)
        results[mode] = eng.run_until_done()
    assert results["sequential"] == results["batched"]


def test_prefill_dispatch_counts(setup):
    """B slots of S-token prompts must cost O(ceil(S/chunk)) prefill
    dispatches on the batched path vs B*S on the sequential path."""
    cfg, params = setup
    S, chunk, B = 33, 8, 3
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, S).astype(np.int32)
               for _ in range(B)]
    engines = {}
    for mode in ("sequential", "batched"):
        eng = _engine(cfg, params, mode)
        for p in prompts:
            eng.add_request(p, max_new_tokens=2)
        eng.run_until_done()
        engines[mode] = eng
    n_chunks = -(-(S - 1) // chunk)
    assert engines["batched"].dispatch_counts["prefill"] == n_chunks
    assert engines["sequential"].dispatch_counts["prefill"] == B * (S - 1)


def test_prefill_chunk_cache_matches_sequential_decode(setup):
    """Unit-level: the chunked flash prefill writes the same K/V the
    teacher-forced decode loop writes (per-slot valid positions)."""
    cfg, params = setup
    B, L, C = 3, 64, 8
    rng = np.random.default_rng(2)
    plens = [5, 12, 1]
    prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in plens]

    cache_s = init_params(T.cache_defs(cfg, B, L), KEY)
    lens = np.zeros((B,), np.int64)
    dec = jax.jit(lambda p, t, c, l: T.decode_step(cfg, p, t, c, l))
    import jax.numpy as jnp
    for slot, pr in enumerate(prompts):
        for tok in pr[:-1]:
            t = jnp.zeros((B, 1), jnp.int32).at[slot, 0].set(int(tok))
            _, cache_s = dec(params, t, cache_s, jnp.asarray(lens, jnp.int32))
            lens[slot] += 1

    cache_b = init_params(T.cache_defs(cfg, B, L), KEY)
    S = max(p - 1 for p in plens)
    n_chunks = -(-S // C)
    toks = np.zeros((B, n_chunks * C), np.int32)
    valid = np.zeros((B, n_chunks * C), bool)
    for slot, pr in enumerate(prompts):
        toks[slot, :len(pr) - 1] = pr[:-1]
        valid[slot, :len(pr) - 1] = True
    for c in range(n_chunks):
        vc = valid[:, c * C:(c + 1) * C]
        if not vc.any():
            break
        cache_b = jax.jit(
            lambda p, t, cc, v, _c=c: T.prefill_chunk(cfg, p, t, cc, v,
                                                      offset=_c * C)
        )(params, jnp.asarray(toks[:, c * C:(c + 1) * C]), cache_b,
          jnp.asarray(vc))

    # compare only positions each slot validly wrote: the sequential decode
    # path clobbers other rows' cur_len position as a side effect
    valid_pos = (np.arange(L)[None, :]
                 < (np.array(plens) - 1)[:, None])          # (B, L)
    for pos in cache_s:
        for k in cache_s[pos]:
            a = np.asarray(cache_s[pos][k], np.float32)
            b = np.asarray(cache_b[pos][k], np.float32)
            m = valid_pos[None, :, :, None, None] if a.ndim == 5 \
                else valid_pos[None, :, :, None]
            np.testing.assert_allclose(a * m, b * m, rtol=2e-2, atol=2e-2,
                                       err_msg=f"{pos}/{k}")


def test_ssm_family_falls_back_to_sequential():
    """RWKV stacks can't batch-prefill (recurrent state); the engine must
    route them down the sequential path and still serve correctly."""
    cfg = get_arch("rwkv6-7b").reduced()
    assert not T.supports_batched_prefill(cfg)
    params = init_params(T.param_defs(cfg), KEY)
    eng = _engine(cfg, params, "batched", slots=2, max_len=32)
    rng = np.random.default_rng(3)
    rids = [eng.add_request(rng.integers(0, cfg.vocab_size, 4),
                            max_new_tokens=3) for _ in range(3)]
    res = eng.run_until_done()
    assert sorted(res) == sorted(rids)
    assert all(len(v) == 3 for v in res.values())


def test_decode_is_single_dispatch_single_sync(setup):
    """Sample-on-device: sampling + length/termination update are folded
    into the jitted decode step, so a generation step costs exactly ONE
    dispatch and ONE host sync (the token/done/len fetch)."""
    cfg, params = setup
    rec = TraceRecorder()
    eng = _engine(cfg, params, recorder=rec)
    rng = np.random.default_rng(7)
    for p in (4, 11, 2):
        eng.add_request(rng.integers(0, cfg.vocab_size, p), max_new_tokens=5)
    eng.run_until_done()
    gen_steps = sum(r["phase"] == "generation" for r in _routes(rec))
    assert eng.dispatch_counts["decode"] == gen_steps
    assert eng.host_syncs == gen_steps


def test_temperature_sampling_on_device(setup):
    """The fused step's categorical path: deterministic under a fixed seed,
    still one sync per step, and termination still lands on budget."""
    cfg, params = setup
    outs = []
    for _ in range(2):
        eng = ServeEngine(cfg, params,
                          ServeConfig(max_slots=2, max_len=64,
                                      temperature=0.8, seed=9,
                                      prefill_chunk=8))
        rng = np.random.default_rng(8)
        eng.add_request(rng.integers(0, cfg.vocab_size, 6), max_new_tokens=4)
        outs.append(eng.run_until_done())
        assert eng.host_syncs == eng.dispatch_counts["decode"]
    assert outs[0] == outs[1]
    assert all(len(v) == 4 for v in outs[0].values())


def test_bucketed_admission_cuts_prefill_dispatches(setup):
    """Length-bucketed admission: short/long interleaved arrivals must cost
    fewer prefill dispatches than FIFO (homogeneous waves), produce MORE
    useful token-slots per dispatch, and emit identical greedy tokens."""
    cfg, params = setup
    rng = np.random.default_rng(9)
    plens = [4, 33, 4, 33]              # FIFO pairs a straggler per wave
    prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in plens]
    engines = {}
    for adm in ("fifo", "bucketed"):
        eng = ServeEngine(cfg, params,
                          ServeConfig(max_slots=2, max_len=64,
                                      prefill_chunk=8, admission=adm))
        for p in prompts:
            eng.add_request(p, max_new_tokens=2)
        engines[adm] = (eng, eng.run_until_done())
    fifo, bucketed = engines["fifo"], engines["bucketed"]
    assert fifo[1] == bucketed[1]       # same tokens per rid either way
    # fifo: two {4,33} waves of 4 chunks each; bucketed: {4,4}=1 + {33,33}=4
    assert bucketed[0].dispatch_counts["prefill"] \
        < fifo[0].dispatch_counts["prefill"]

    def useful(eng):
        return (eng.prefill_stats["valid_tokens"]
                / eng.prefill_stats["token_slots"])
    assert useful(bucketed[0]) > useful(fifo[0])


def test_bucketed_admission_ages_long_prompts(setup):
    """Aging bounds starvation: a long prompt queued behind a sustained
    stream of short arrivals must still be admitted (its effective bucket
    drops by one per wave it is passed over)."""
    cfg, params = setup
    eng = ServeEngine(cfg, params,
                      ServeConfig(max_slots=1, max_len=64, prefill_chunk=4))
    rng = np.random.default_rng(10)
    long_rid = eng.add_request(
        rng.integers(0, cfg.vocab_size, 30), max_new_tokens=2)
    results = {}
    # a fresh short request EVERY step: arrivals outpace service, so the
    # queue always holds a lower-bucket candidate when the slot frees —
    # without aging the long prompt would never be chosen
    for _ in range(40):
        eng.add_request(rng.integers(0, cfg.vocab_size, 3),
                        max_new_tokens=2)
        for rid, tok in eng.step():
            results.setdefault(rid, []).append(tok)
    assert long_rid in results           # admitted despite constant load


def test_pas_log_records_phases(setup):
    """Every recorded dispatch carries its phase and PAS route."""
    cfg, params = setup
    rec = TraceRecorder()
    eng = _engine(cfg, params, recorder=rec)
    rng = np.random.default_rng(4)
    eng.add_request(rng.integers(0, cfg.vocab_size, 12), max_new_tokens=3)
    eng.run_until_done()
    routes = _routes(rec)
    phases = [r["phase"] for r in routes]
    assert "summarization" in phases and "generation" in phases
    for r in routes:
        assert r["ffn_route"] in ("gemm", "gemv")


def test_pallas_prefill_refuses_ragged_tail(setup):
    """With use_pallas, a last chunk that overhangs the cache (max_len not a
    multiple of the chunk) raises rather than running the XLA twin."""
    import dataclasses
    import functools
    cfg, params = setup
    cfg = dataclasses.replace(cfg, use_pallas=True)
    cache = init_params(T.cache_defs(cfg, 2, 40), KEY)
    tokens, valid = np.zeros((2, 16), np.int32), np.ones((2, 16), bool)
    step = functools.partial(T.prefill_chunk, cfg, offset=32)
    with pytest.raises(ValueError, match="does not tile"):
        jax.eval_shape(step, params, tokens, cache, valid)


def test_prefill_chunk_row_slot_matches_slot_grid(setup):
    """Unit-level: ``T.prefill_chunk`` over rows that name their slots, one
    row per dispatch or several with a padding row, writes the same K/V as
    the slot-grid call, and leaves the other slots' rows as they were."""
    import jax.numpy as jnp
    cfg, params = setup
    B, L, C = 4, 64, 8
    rng = np.random.default_rng(11)
    plens = {2: 14, 0: 7}                       # slots 1 and 3 stay out
    toks = np.zeros((B, 2 * C), np.int32)
    valid = np.zeros((B, 2 * C), bool)
    for slot, n in plens.items():
        toks[slot, :n - 1] = rng.integers(0, cfg.vocab_size, n - 1)
        valid[slot, :n - 1] = True
    leaves, tree = jax.tree.flatten(init_params(T.cache_defs(cfg, B, L), KEY))
    keys = jax.random.split(KEY, len(leaves))
    cache0 = jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape).astype(a.dtype)
        for k, a in zip(keys, leaves)])
    step = {c: jax.jit(functools.partial(T.prefill_chunk, cfg, offset=c * C))
            for c in range(2)}

    def run(groups):
        cache = cache0
        for c in range(2):
            w = slice(c * C, (c + 1) * C)
            for rows in groups:
                if rows is None:
                    cache = step[c](params, toks[:, w], cache, valid[:, w])
                    continue
                v = valid[rows, w]
                v[len(set(rows)):] = False       # a repeated row pads
                cache = step[c](params, toks[rows, w], cache, v,
                                jnp.asarray(rows, jnp.int32))
        return cache

    want = run([None])
    for groups in ([[2], [0]], [[0, 2]], [[2, 0, 2]]):
        got = run(groups)
        for pos in want:
            for k in want[pos]:
                a = np.asarray(want[pos][k], np.float32)
                b = np.asarray(got[pos][k], np.float32)
                np.testing.assert_array_equal(a, b,
                                              err_msg=f"{groups} {pos}/{k}")
                np.testing.assert_array_equal(
                    b[:, [1, 3]],
                    np.asarray(cache0[pos][k], np.float32)[:, [1, 3]])


# (prefill_chunk, max_slots, max_len, prompt lengths): one row per dispatch
# at 256-token chunks, two rows (the last group padded) at 128, the slot
# grid at 8; every set spans several chunks and mixes lengths
GRIDS = {
    "rows1": (256, 4, 512, (300, 40, 470, 260, 3, 130)),
    "rows2": (128, 4, 512, (300, 40, 470, 260, 3, 130)),
    "slot_grid": (8, 4, 64, (5, 17, 30, 9, 2, 22)),
}


def _grid_engine(cfg, params, grid, policy="serial"):
    C, B, L, _ = GRIDS[grid]
    return ServeEngine(cfg, params, ServeConfig(
        max_slots=B, max_len=L, prefill_chunk=C, policy=policy))


def _slot_grid_engine(cfg, params, grid, monkeypatch, policy="serial"):
    """The same engine with every prefill dispatch on the slot grid
    (row i = slot i, no ``row_slot``)."""
    C, B, _, _ = GRIDS[grid]
    with monkeypatch.context() as m:
        m.setattr(engine_mod, "PREFILL_ROW_TOKENS", B * C)
        eng = _grid_engine(cfg, params, grid, policy)
    assert eng._prefill_rows == B
    return eng


def _rows_computed(eng, grid):
    C, B, _, _ = GRIDS[grid]
    return engine_mod.prefill_rows(B, C) * C * eng.dispatch_counts["prefill"]


@pytest.mark.parametrize("policy", ["serial", "interleaved"])
@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_row_grid_matches_slot_grid(setup, monkeypatch, grid, policy):
    """Waves of several requests, then of one as slots free, with prompts
    over several chunks: the row grid emits the slot grid's greedy tokens,
    and ``token_slots`` counts the rows each dispatch computed."""
    cfg, params = setup
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in GRIDS[grid][3]]
    engines = [_grid_engine(cfg, params, grid, policy),
               _slot_grid_engine(cfg, params, grid, monkeypatch, policy)]
    out = []
    for eng in engines:
        for p in prompts:
            eng.add_request(p, max_new_tokens=4)
        out.append(eng.run_until_done())
    assert out[0] == out[1]
    rows, slot_grid = engines
    assert rows.prefill_stats["token_slots"] == _rows_computed(rows, grid)
    assert rows.prefill_stats["valid_tokens"] \
        == slot_grid.prefill_stats["valid_tokens"]
    if rows._prefill_rows < rows.scfg.max_slots:
        assert rows.prefill_stats["token_slots"] \
            < slot_grid.prefill_stats["token_slots"]


@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_row_grid_leaves_other_slots(setup, grid):
    """A wave's prefill dispatches write no K/V row of a slot outside the
    wave: a resident request's and a free slot's stay bit-identical."""
    cfg, params = setup
    _, B, _, lens = GRIDS[grid]
    rng = np.random.default_rng(13)
    eng = _grid_engine(cfg, params, grid)
    eng.add_request(rng.integers(0, cfg.vocab_size, lens[0]))
    eng.prefill_wave(eng.admit_wave(limit=1))
    for n in lens[1:3]:
        eng.add_request(rng.integers(0, cfg.vocab_size, n))
    wave = eng.admit_wave(limit=2)
    outside = [s for s in range(B) if s not in {s for s, _ in wave}]
    before = {k: np.asarray(v)
              for k, v in engine_mod._flatten_cache(eng.cache).items()}
    job = eng.build_prefill_job(wave)
    d0 = eng.dispatch_counts["prefill"]
    t0 = eng.prefill_stats["token_slots"]
    while not job.done:
        eng.dispatch_prefill_chunk(job)
    C = GRIDS[grid][0]
    assert eng.prefill_stats["token_slots"] - t0 == \
        engine_mod.prefill_rows(B, C) * C * (eng.dispatch_counts["prefill"]
                                             - d0)
    for k, v in engine_mod._flatten_cache(eng.cache).items():
        np.testing.assert_array_equal(np.asarray(v)[:, outside],
                                      before[k][:, outside], err_msg=k)


@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_row_grid_restored_prefix(setup, monkeypatch, grid):
    """A request whose prefix is restored from a snapshot (``prefill_start``
    inside its second chunk) prefills only its suffix beside a fresh
    request: the row grid emits the slot grid's tokens and a full
    prefill's."""
    cfg, params = setup
    C, _, _, lens = GRIDS[grid]
    rng = np.random.default_rng(14)
    prompt = rng.integers(0, cfg.vocab_size, max(lens)).astype(np.int32)
    other = rng.integers(0, cfg.vocab_size, lens[1]).astype(np.int32)
    src = _grid_engine(cfg, params, grid)
    src.add_request(prompt, gid=0)
    src.prefill_wave(src.admit_wave())
    snap = src.export_kv_snapshot()[0]
    P = C + C // 2
    assert P < len(prompt) - 1
    restore = {"prefix_len": P, "snapshot_step": 0,
               "cache": {k: v[:, :P] for k, v in snap["cache"].items()}}

    def serve(eng, restored):
        rid = eng.add_request(prompt, max_new_tokens=4, gid=0,
                              restore=restore if restored else None)
        eng.add_request(other, max_new_tokens=4)
        out = eng.run_until_done()
        return out, rid

    rows = _grid_engine(cfg, params, grid)
    got, rid = serve(rows, True)
    want, _ = serve(_slot_grid_engine(cfg, params, grid, monkeypatch), True)
    fresh, _ = serve(_grid_engine(cfg, params, grid), False)
    assert got == want == fresh
    assert rows.snapshot_stats["restored_tokens"] == P
    assert rows.prefill_stats["valid_tokens"] == \
        len(prompt) - 1 - P + len(other) - 1
    assert rows.prefill_stats["token_slots"] == _rows_computed(rows, grid)
