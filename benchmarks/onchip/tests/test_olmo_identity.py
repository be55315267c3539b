"""``olmo-1b`` reads the same through its architecture module as it did
when the harness knew only OLMo: the same weights from a seed, the same
reference logits in float32 and in the fp8 control, and the same counts.
The digests and numbers were taken from the harness before the
architecture modules (``weights.make_weights(config, seed)``,
``reference.logits_at(config, norm, ...)``, ``flops.Counts(conf)``); a
change here moves the rooflines, the MFU or the check of both olmo cells.
"""
import hashlib

import numpy as np
import pytest

import cells
import weights

# sha256 (first 16 hex digits) of each leaf's bytes at rehearsal size
WEIGHTS = {
    3: {
        "embed": "00874a0f210a0d5e",
        "layers/w_down": "fc9098d7e76ef80e",
        "layers/w_gate": "d19a80484db2bd1a",
        "layers/w_up": "067f17285dc606ba",
        "layers/wk": "a88b8255d8ceda2a",
        "layers/wo": "67ab9cf3d5605630",
        "layers/wq": "05d547ab78e17ab4",
        "layers/wv": "afd3110e2d6a8510",
    },
    2**31 + 17: {
        "embed": "037f65a5bca6fa25",
        "layers/w_down": "20424c424f407add",
        "layers/w_gate": "f1ac0bb27f343ea9",
        "layers/w_up": "28cf3cb526764d11",
        "layers/wk": "ea285f09dd679738",
        "layers/wo": "844da41f368f886f",
        "layers/wq": "fd4dfcd489048a44",
        "layers/wv": "74720b24db571469",
    },
}
# the same digest of logits_at at rehearsal size, weights of seed 3
LOGITS = {"f32": "2f8f3b8872128ac6", "fp8": "c9ab40d615ec8778"}


def _digest(a) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()[:16]


def _flat(w, prefix=""):
    out = {}
    for k, v in w.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


@pytest.fixture(scope="module")
def small():
    conf = cells.load("olmo-1b.sum", rehearsal=True).conf
    return conf, cells.arch(conf)


@pytest.mark.parametrize("seed", sorted(WEIGHTS))
def test_olmo_weights_are_unchanged(small, seed):
    conf, arch = small
    w = weights.make_weights(arch.shapes(conf), seed)
    assert {k: _digest(v) for k, v in _flat(w).items()} == WEIGHTS[seed]


@pytest.mark.parametrize("mode", sorted(LOGITS))
def test_olmo_reference_logits_are_unchanged(small, mode):
    conf, arch = small
    w = weights.make_weights(arch.shapes(conf), 3)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, arch.dims(conf)["vocab"],
                          (2, 256)).astype(np.int32)
    pos = np.stack([np.arange(100, 108),
                    np.arange(240, 248)]).astype(np.int32)
    assert _digest(arch.logits_at(conf, w, tokens, pos, mode)) == \
        LOGITS[mode]


def test_olmo_counts_are_unchanged():
    conf = cells.load("olmo-1b.sum").conf
    c = cells.arch(conf).Counts(conf)
    assert [c.prefill_flops(n) for n in (1, 255, 781)] == \
        [2147614720.0, 551886520320.0, 1717210316800.0]
    assert c.prefill_bytes(3, 700) == 6534201344
    assert [c.decode_flops(n) for n in (1, 2047)] == \
        [2353659904, 2621833216]
    assert c.decode_step_bytes([10, 500, 2047]) == 2689073152
