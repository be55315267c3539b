"""The check catches a broken timed path. Each fault is planted in the
program's step functions underneath the engine (the engine's jitted
entries are rebuilt around the broken function) and a whole rehearsal run
must come out with ``correct`` false:

  state_unchanged  the decode step returns the cache it was given, so no
                   generated token's key and value is ever written
  half_batch       prefill writes the keys and values of every other slot
                   only; requests in the odd slots decode over zeros
  token_altered    the decode step serves the next token id instead of the
                   one it computed, at one position in four

The exchange between chips cannot fail here: every cell runs on one chip.

half_batch shows only in a request that sat in an odd slot. The check's
sample holds one whenever such a request finished: it takes one request
from each part of the batch, even and odd slots of either half
(``run.sample_finished``).
"""
import jax.numpy as jnp
import pytest

import system  # noqa: F401  (puts the program on the path)
from repro.models import transformer as T
from repro.serve import engine as E

from rehearse import rehearse
from test_rehearsal import CELLS

_decode = T.decode_and_sample
_prefill = T.prefill_chunk


def state_unchanged(cfg, params, cache, *a, **k):
    out = _decode(cfg, params, cache, *a, **k)
    return (out[0], cache) + tuple(out[2:])


def token_altered(cfg, params, cache, *a, **k):
    fetch, cache, toks, lens, *rest = _decode(cfg, params, cache, *a, **k)
    toks = jnp.where(lens % 4 == 0, (toks + 1) % cfg.vocab_size, toks)
    return (fetch.at[0].set(toks), cache, toks, lens, *rest)


def half_batch(cfg, params, tokens, cache, tok_valid, *, offset):
    return _prefill(cfg, params, tokens, cache,
                    tok_valid.at[1::2].set(False), offset=offset)


FAULTS = {"state_unchanged": ("decode_and_sample", state_unchanged),
          "token_altered": ("decode_and_sample", token_altered),
          "half_batch": ("prefill_chunk", half_batch)}


def _clear():
    for name in dir(E):
        fn = getattr(E, name)
        if name.startswith("_jit_") and hasattr(fn, "cache_clear"):
            fn.cache_clear()


@pytest.fixture
def plant(monkeypatch):
    def _plant(fault):
        attr, fn = FAULTS[fault]
        monkeypatch.setattr(T, attr, fn)
        _clear()
    yield _plant
    monkeypatch.undo()
    _clear()


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(plant, cell, fault):
    plant(fault)
    out = rehearse(cell, seed=11, seconds=2.0)
    assert not out["correct"], (fault, out["checks"])
