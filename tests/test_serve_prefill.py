"""Phase-separated serving: batched prefill equivalence + dispatch shape."""
import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve import ServeConfig, ServeEngine

KEY = jax.random.PRNGKey(0)


def _engine(cfg, params, mode="batched", chunk=8, slots=3, max_len=64):
    return ServeEngine(cfg, params,
                       ServeConfig(max_slots=slots, max_len=max_len,
                                   prefill_mode=mode, prefill_chunk=chunk))


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
            m = valid_pos[None, :, None, :, None] if a.ndim == 5 \
                else valid_pos[None, :, None, :]
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
    eng = _engine(cfg, params)
    rng = np.random.default_rng(7)
    for p in (4, 11, 2):
        eng.add_request(rng.integers(0, cfg.vocab_size, p), max_new_tokens=5)
    eng.run_until_done()
    gen_steps = sum(e["phase"] == "generation" for e in eng.pas_log)
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
    cfg, params = setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(4)
    eng.add_request(rng.integers(0, cfg.vocab_size, 12), max_new_tokens=3)
    eng.run_until_done()
    phases = [e["phase"] for e in eng.pas_log]
    assert "summarization" in phases and "generation" in phases
    for e in eng.pas_log:
        assert e["ffn_route"] in ("gemm", "gemv")


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
