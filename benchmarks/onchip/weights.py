"""Random weights for a configuration, made on the device from the seed.

The benchmark makes the weights itself, in one jitted program, in the type
they are served in (bfloat16), so that the plain reference and the system
under test read the same numbers and neither depends on the other. The
architecture's ``shapes(conf)`` (``archs/``) lists the leaves, in the plain
layout of the published architecture; ``system.py`` rearranges them into
the program's parameter tree.

A leaf's path names it: ``layers/<name>`` is stacked over the layers (its
first axis) and drawn layer by layer; ``a/b`` nests ``b`` under ``a``.
Leaf ``i`` in the order listed draws from ``fold_in(key, i)``, so a leaf
appended to the list leaves every earlier leaf as it was. Kinds:

    embed    normal, std 0.02
    matrix   normal, std 1/sqrt(fan-in)
    scale    uniform in [0.5, 1.5]: a norm's scale, random (not the
             published ones) so that a program that drops or misplaces a
             scale fails the check
"""
from __future__ import annotations

import numpy as np


def weight_key(seed: int):
    """A PRNG key that depends on every bit of ``seed`` (``PRNGKey`` alone
    keeps only the low 32 bits of a larger integer)."""
    import jax
    word = int(np.random.SeedSequence(seed & (2**64 - 1)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def nest(flat: dict) -> dict:
    """{"a/b": x} -> {"a": {"b": x}}."""
    out = {}
    for path, v in flat.items():
        *outer, leaf = path.split("/")
        d = out
        for p in outer:
            d = d.setdefault(p, {})
        d[leaf] = v
    return out


def abstract(spec: dict, sharding) -> dict:
    """The leaves of ``spec`` as bfloat16 shapes on ``sharding``, nested."""
    import jax
    import jax.numpy as jnp
    return nest({k: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
                 for k, (s, _, _) in spec.items()})


def make_weights(spec: dict, seed: int):
    """Every leaf of ``spec`` (an architecture's ``shapes(conf)``),
    bfloat16, on the default device, from one jitted program. Stacked
    leaves are drawn layer by layer (``lax.map``) so float32 temporaries
    stay one layer in size."""
    import jax
    import jax.numpy as jnp

    def draw(key, shape, fan_in, kind):
        if kind == "scale":
            z = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        else:
            z = jax.random.normal(key, shape, jnp.float32)
            if kind == "embed":
                z = 0.02 * z
            else:
                z = z * (fan_in ** -0.5)
        return z.astype(jnp.bfloat16)

    def build(key):
        out = {}
        for i, (name, (shape, fan_in, kind)) in enumerate(spec.items()):
            k = jax.random.fold_in(key, i)
            if name.startswith("layers/"):
                keys = jax.random.split(k, shape[0])
                out[name] = jax.lax.map(
                    lambda kk, s=shape[1:], f=fan_in, t=kind: draw(kk, s, f, t),
                    keys)
            else:
                out[name] = draw(k, shape, fan_in, kind)
        return out

    return nest(jax.jit(build)(weight_key(seed)))
