"""The norm's epsilon comes from ``ModelConfig.norm_eps``.

An entry that leaves it unset resolves to the epsilon ``apply_norm`` has
always applied to its norm (1e-6 for RMSNorm, 1e-5 for the LayerNorms), so
its programs are unchanged; an entry that states the published value keeps
it. On an input whose variance is near 1e-5, 1e-5 and 1e-6 give visibly
different outputs, so each case also shows that ``apply_norm`` applies the
configured value and not the other.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.models.layers import apply_norm

# what apply_norm applied before ModelConfig had norm_eps
BEFORE = {"rmsnorm": 1e-6, "layernorm": 1e-5, "np_layernorm": 1e-5}
# entries that state their published epsilon
STATED = {"phi3-medium-14b": 1e-5}     # config.json rms_norm_eps
OVERRIDDEN = ("llama3.2-1b", "granite-20b", "olmo-1b")   # one per norm kind

CASES = ([(name, None) for name in sorted(ARCHS)]
         + [(name, eps) for name in OVERRIDDEN for eps in (1e-5, 1e-6)])


def formula(kind, x, p, eps):
    """The norm in float64: RMSNorm, LayerNorm, or OLMo's LayerNorm with no
    scale and no bias."""
    x = x.astype(np.float64)
    if kind == "rmsnorm":
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"]
    c = x - x.mean(-1, keepdims=True)
    y = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + eps)
    return y * p["scale"] + p["bias"] if kind == "layernorm" else y


@pytest.mark.parametrize("arch,eps", CASES,
                         ids=[f"{a}-{e or 'registry'}" for a, e in CASES])
def test_apply_norm_applies_norm_eps(arch, eps):
    cfg = get_arch(arch)
    if eps is None:
        eps = STATED.get(arch, BEFORE[cfg.norm])
        assert cfg.norm_eps == eps
        assert cfg.reduced().norm_eps == eps
    else:
        cfg = dataclasses.replace(cfg, norm_eps=eps)
    rng = np.random.default_rng(0)
    d = cfg.d_model
    x = (rng.standard_normal((2, 3, d)) * np.sqrt(1e-5)).astype(np.float32)
    p = {}
    if cfg.norm != "np_layernorm":
        p["scale"] = rng.uniform(0.5, 1.5, d).astype(np.float32)
    if cfg.norm == "layernorm":
        p["bias"] = rng.uniform(-0.1, 0.1, d).astype(np.float32)
    got = np.asarray(apply_norm(cfg, {k: jnp.asarray(v) for k, v in p.items()},
                                jnp.asarray(x)))
    np.testing.assert_allclose(got, formula(cfg.norm, x, p, eps),
                               rtol=1e-5, atol=1e-5)
    other = 1e-6 if eps == 1e-5 else 1e-5
    assert not np.allclose(got, formula(cfg.norm, x, p, other), rtol=1e-2)
